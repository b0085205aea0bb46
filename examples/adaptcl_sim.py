"""End-to-end driver: the faithful AdaptCL reproduction (paper Alg. 1+2).

Runs the full collaborative-learning simulation — 10 heterogeneous workers,
synchronous rounds, dynamic pruned-rate learning, CIG-BNscalor pruning,
By-worker aggregation — against the FedAVG-S baseline, and prints the
Table II-style comparison.

    PYTHONPATH=src python examples/adaptcl_sim.py [--rounds 30] [--sigma 2] \
        [--workers 10] [--engine masked] [--scenario 0.5,0.1,0.02]

``--engine masked`` runs the resident fleet engine (core.fleet.FleetState):
all workers live as [W, ...] base-shape stacks on device, so host wall-clock
is ~flat in worker count — try ``--workers 200 --engine masked``.

``--scenario C,dropout,churn`` turns on the flaky-fleet scenario layer
(per-round client sampling with fraction C, straggler dropout, slot churn).
Async methods accept sampling only (C,0,0): a static C*W cohort joins the
event loop and the resident engine sizes device compute to it.

``--compute block_skip`` (with ``--engine masked``) dispatches the convs +
head through the ``kernels/pruned_matmul`` block-skip Pallas kernel, so a
pruned worker's device FLOPs track its retention (``--compute-blocks``
sets the tile sizes; shrink them for CPU interpret runs).

``--mesh-devices N`` (with ``--engine fused``, sync methods) shards the
resident ``[W, ...]`` stacks over an N-device fleet mesh axis — the fused
scan runs per shard with two-tier psum aggregation; on CPU expose virtual
devices first: ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

``--methods`` picks the frameworks to compare (first = baseline for the
speedup line), e.g. the async schedulers on the resident engine:

    PYTHONPATH=src python examples/adaptcl_sim.py --engine masked \
        --methods fedasync_s,ssp_s,dcasgd_s --async-window 50 --rounds 6
"""
import argparse

import numpy as np

from repro.core.scenario import ScenarioConfig
from repro.core.simulation import SimConfig, run_simulation
from repro.core.timing import HeterogeneityConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=30)
    ap.add_argument("--sigma", type=float, default=2.0)
    ap.add_argument("--noniid", type=float, default=80.0)
    ap.add_argument("--workers", type=int, default=10)
    ap.add_argument("--engine", default="sequential",
                    choices=("sequential", "bucketed", "masked", "fused"))
    ap.add_argument("--round-fusion", type=int, default=0,
                    help="fused engine: max rounds per on-device lax.scan "
                         "chunk (0 = fuse up to the next prune-rate-learning "
                         "event)")
    ap.add_argument("--compute", default="dense",
                    choices=("dense", "block_skip"),
                    help="masked engine's device compute path: block_skip "
                         "dispatches convs + head through the "
                         "kernels/pruned_matmul block-skip Pallas kernel so "
                         "device FLOPs track retention (requires --engine "
                         "masked; interpret mode on CPU)")
    ap.add_argument("--compute-blocks", default="128,128,128",
                    metavar="BM,BN,BK",
                    help="pruned_matmul tile sizes: multiples of 128 on "
                         "TPU; smaller (e.g. 128,8,8) only for fine-grained "
                         "CPU/interpret runs")
    ap.add_argument("--mesh-devices", type=int, default=0, metavar="N",
                    help="mesh-sharded fleet: shard the [W, ...] stacks over "
                         "N devices (fused sync engine only; W %% N == 0). "
                         "On a CPU-only host expose virtual devices first: "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=8")
    ap.add_argument("--scenario", default=None, metavar="C,DROPOUT,CHURN",
                    help="client sampling fraction, dropout prob, churn prob")
    ap.add_argument("--methods", default="fedavg_s,adaptcl",
                    help="comma list of frameworks to compare (first = "
                         "baseline): fedavg, fedavg_s, adaptcl, fedasync_s, "
                         "ssp_s, dcasgd_s")
    ap.add_argument("--async-window", type=float, default=0.0,
                    help="virtual window batching async commits into one "
                         "fleet call (async methods only)")
    args = ap.parse_args()

    scenario = None
    if args.scenario:
        c, drop, churn = (float(v) for v in args.scenario.split(","))
        scenario = ScenarioConfig(participation=c, dropout=drop, churn=churn)

    mesh = None
    if args.mesh_devices:
        from repro.launch.mesh import make_fleet_mesh

        mesh = make_fleet_mesh(args.mesh_devices)

    methods = [m.strip() for m in args.methods.split(",") if m.strip()]
    results = {}
    for method in methods:
        sim = SimConfig(
            method=method,
            rounds=args.rounds,
            prune_interval=5,
            num_workers=args.workers,
            noniid_s=args.noniid,
            het=HeterogeneityConfig(num_workers=args.workers, sigma=args.sigma),
            engine=args.engine,
            round_fusion=args.round_fusion,
            compute=args.compute,
            compute_blocks=tuple(int(v) for v in args.compute_blocks.split(",")),
            scenario=scenario,
            async_window=args.async_window,
            mesh=mesh,
        )
        r = run_simulation(sim)
        results[method] = r
        print(f"[{method:9s}] best_acc={r.best_acc:.3f} time={r.total_time:.0f}s "
              f"param_red={r.param_reduction:.1%} "
              f"(host: {r.walltime_s:.1f}s, {r.recompiles} compiles, "
              f"{r.host_roundtrips} roundtrips, engine={r.engine})")
        if mesh is not None:
            print(f"            mesh: {r.n_devices} devices x "
                  f"W_local={args.workers // r.fleet_axis_size} "
                  f"spec={r.shard_spec}")
        if args.compute == "block_skip":
            print(f"            compute=block_skip: "
                  f"flops_exec/ideal={r.flops_executed / max(r.flops_ideal, 1e-9):.3f} "
                  f"blocks/img(final)={r.blocks_per_image_final:.0f}")
        if method == "adaptcl":
            print(f"            retentions={[round(g, 2) for g in r.retentions]}")
            hs = [f"{h:.2f}" for _, h in r.het_traj[:: max(1, args.rounds // 8)]]
            print(f"            heterogeneity trajectory: {' -> '.join(hs)}")

    if len(methods) > 1:
        base, last = results[methods[0]], results[methods[-1]]
        note = "  (paper at sigma=2: 1.78x)" if methods == ["fedavg_s", "adaptcl"] else ""
        print(f"\n{methods[-1]} vs {methods[0]} speedup: "
              f"{base.total_time / last.total_time:.2f}x{note}   "
              f"dAcc={last.best_acc - base.best_acc:+.3f}")


if __name__ == "__main__":
    main()
