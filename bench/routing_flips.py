"""How many routing choices of a sigmoid-routed expert family differ between
the program and the reference on the same model and the same batch.

    python bench/routing_flips.py --workload <cell> --seed <n> [--batch 4]

For the initial model and for the global model after the cell's set-up
simulation, each side runs one forward pass over the first ``--batch``
training samples of the first worker's shard: the program at its own
precision (``DeepSeekV3Config.apply``), the reference at ``HIGHEST``.  Each
expert layer's top-``k`` choices are read where they are made (a host
callback beside the program's ``moe.sigmoid_route`` and beside the
reference's ``_experts``, which chooses by the same formula).  Prints one
JSON line: per model and layer, the tokens, the tokens whose chosen set
differs, and the choices that differ (half the symmetric difference).
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
if sys.path and Path(sys.path[0] or ".").resolve() == BENCH:
    sys.path[0] = str(BENCH.parent)   # bench/trace.py must not shadow the standard library's trace

from bench import run  # noqa: E402


def choices(fn, record):
    """``fn()``'s expert choices per layer, in layer order."""
    record.clear()
    fn()
    import jax

    jax.effects_barrier()
    return [np.sort(c, axis=-1) for _, c in sorted(record, key=lambda kc: kc[0])]


def compare(prog, ref) -> list:
    out = []
    for l, (a, b) in enumerate(zip(prog, ref)):
        diff = np.array([len(set(x) ^ set(y)) // 2 for x, y in zip(a, b)])
        out.append({"layer": l, "tokens": int(len(a)), "tokens_differing": int((diff > 0).sum()),
                    "choices_differing": int(diff.sum())})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--batch", type=int, default=4)
    args = ap.parse_args(argv)
    wl, cfg = run.load_cell(args.workload)
    run.start_jax(int(wl["chips"]))
    import jax
    import jax.numpy as jnp

    from bench.models import family
    from repro.models import moe

    model = cfg["model"]
    fam = family(model["kind"])
    task, pseed, _ = run.make_inputs(cfg, args.seed)
    from bench.data import partition_noniid

    shard = partition_noniid(task.y_train, cfg["num_workers"], cfg["noniid_s"], pseed)[0]
    x = task.x_train[shard[: args.batch]]
    record = []

    def keep(layer, c):
        record.append((len(record) if layer is None else int(layer), np.asarray(c)))

    route = moe.sigmoid_route

    def traced_route(xx, w_router, bias, spec):
        # the program's layers call in order: an ordered callback keeps it
        idx, g = route(xx, w_router, bias, spec)
        jax.debug.callback(lambda c: keep(None, c), idx, ordered=True)
        return idx, g

    experts = fam._experts

    def traced_experts(p, pre, m, xn, precision):
        s = jax.nn.sigmoid(jnp.dot(xn.astype(jnp.float32),
                                   p[pre + "moe/router"].astype(jnp.float32),
                                   precision=jax.lax.Precision.HIGHEST))
        layer = int(pre.strip("L/"))
        jax.debug.callback(lambda c: keep(layer, c), jax.lax.top_k(s, m["num_experts_per_tok"])[1]
                           .reshape(-1, m["num_experts_per_tok"]))
        return experts(p, pre, m, xn, precision)

    prog_model = fam.program_model(cfg["name"], model)["model"]
    sim = run.build_sim(wl, cfg, task, pseed)
    result = run.run_captured(sim)[0]
    out = {"seed": args.seed, "batch": args.batch}
    for name, params in (("init", fam.init_params(model, pseed)),
                         ("final", {k: jnp.asarray(v) for k, v in result.global_params.items()})):
        moe.sigmoid_route, fam._experts = traced_route, traced_experts
        try:
            prog_fwd = jax.jit(lambda q, xx: prog_model.apply(q, xx))
            prog = choices(lambda: jax.block_until_ready(prog_fwd(params, jnp.asarray(x))), record)
            ref = choices(lambda: jax.block_until_ready(fam.forward(params, model, jnp.asarray(
                x, jnp.float32))), record)
        finally:
            moe.sigmoid_route, fam._experts = route, experts
        out[name] = compare(prog, ref)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
