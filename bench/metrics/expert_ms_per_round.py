"""Device time of the held experts' grouped matmuls per simulated round,
forward and backward (the rematerialised forward included), from the trace
of the window's first simulation (mean over chips).

On a TPU v5e (JAX 0.9.0) ``lax.ragged_dot`` compiles to custom calls named
``ragged-dot-none.N`` (the grouped matmul) and ``ragged-dot-metadata.N``
(its group offsets, one per layer and pass), which a trace's ``XLA Ops``
line reports under those names; nothing where no such operation ran."""


def read(run):
    if run.trace is None:
        return None
    s = sum(v for k, v in run.trace.op_s.items() if k.startswith("ragged-dot"))
    return 1e3 * s / run.traced_rounds if s > 0 else None
