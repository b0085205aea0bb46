"""Training FLOPs the retained sub-models require in the window (3x forward
at each worker's retained widths, not the full-width FLOPs dense masked
compute executes), over window x chips x the bf16 peak, in percent."""


def read(run):
    if "bf16_flops_per_s" not in run.peaks:
        return None
    return 100.0 * run.required_flops / (run.window_s * run.chips * run.peaks["bf16_flops_per_s"])
