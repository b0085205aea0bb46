"""Training images the fleet's workers processed in the window, over the
window's wall time (host clock, end of the last simulation included)."""


def read(run):
    return run.images / run.window_s
