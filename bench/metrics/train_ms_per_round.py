"""Device time of the training programs per simulated round, from the
trace of the window's first simulation (mean over chips): the runs of the jitted programs whose
name holds ``chunk`` (the fused engine's scan over rounds) or ``train``
(the resident engine's phase programs), on the ``XLA Modules`` line."""


def read(run):
    if run.trace is None:
        return None
    s = sum(v for k, v in run.trace.module_s.items() if "chunk" in k or "train" in k)
    return 1e3 * s / run.traced_rounds if s > 0 else None
