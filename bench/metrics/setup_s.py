"""Process start to the end of the set-up simulation (host clock)."""


def read(run):
    return run.setup_s
