"""Readings that the limits of ``correct`` are set from: for each seed, the
numbers of ``bench/compare.py`` for the program's set-up simulation against
the reference, and for the controls in the program's place.

    python bench/readings.py --workload <cell> --seeds <n,n,...> \
        [--controls bf16,half_batch --control-seeds 3] [--witness-seeds 2]

One process reads every seed, so the program and the reference compile
once.  Controls (on the first ``--control-seeds`` seeds): ``bf16``, the
reference with parameters, optimizer state and activations in bfloat16 at
the default precision; or a fault planted in the reference
(``half_batch``, ``unchanged``).  The witness (on the first
``--witness-seeds`` seeds) is the reference in float32 at the default
precision, the one the program's float32 matmuls run at: its trained rounds
against the reference at ``HIGHEST``, and the program against it.  Each
seed prints one JSON line, with every counted leaf's norms of change
(candidate, reference) after each compared round under ``<side>_norms``.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
if sys.path and Path(sys.path[0] or ".").resolve() == BENCH:
    sys.path[0] = str(BENCH.parent)   # bench/trace.py must not shadow the standard library's trace

from bench import run  # noqa: E402


def control_outcome(name: str, cfg: dict, settings: dict, task, pseed: int, **kw):
    """The reference run as control ``name`` in the program's place."""
    import jax

    from bench import reference

    if name == "bf16":
        return reference.simulate(cfg, settings, task, pseed, dtype=jax.numpy.bfloat16,
                                  precision=jax.lax.Precision.DEFAULT, **kw)
    return reference.simulate(cfg, settings, task, pseed, fault=name, **kw)


def read_seed(wl: dict, cfg: dict, seed: int, controls=(), witness: bool = False) -> dict:
    """The numbers of one seed, against the reference at ``HIGHEST``."""
    import jax

    from bench import compare, reference

    task, pseed, _ = run.make_inputs(cfg, seed)
    settings = run.sim_settings(wl, cfg)
    result, captured = run.run_captured(run.build_sim(wl, cfg, task, pseed))
    prog = compare.from_program(result, captured)
    del result, captured
    gc.collect()
    kw = {"log": run._log, "train_rounds": compare.TRAIN_ROUNDS}
    ref = reference.simulate(cfg, settings, task, pseed, **kw)
    out = {"seed": seed}

    def record(side: str, cand, against=ref):
        out[side] = compare.numbers(cand, against)
        out[f"{side}_worst"] = compare.worst_leaves(cand, against)
        out[f"{side}_norms"] = {t: compare.change_norms(cand, against, t)
                                for t in compare.compared_rounds(cand, against)}

    record("program", prog)
    for c in controls:
        record(c, control_outcome(c, cfg, settings, task, pseed, **kw))
    if witness:
        wit = reference.simulate(cfg, settings, task, pseed, precision=jax.lax.Precision.DEFAULT,
                                 **kw)
        record("witness", wit)
        record("program_vs_witness", prog, wit)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", default="")
    ap.add_argument("--control-seeds", type=int, default=0)
    ap.add_argument("--witness-seeds", type=int, default=0)
    args = ap.parse_args(argv)
    wl, cfg = run.load_cell(args.workload)
    run.start_jax(int(wl["chips"]))
    controls = [c for c in args.controls.split(",") if c]
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        out = read_seed(wl, cfg, seed, controls if i < args.control_seeds else (),
                        witness=i < args.witness_seeds)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
