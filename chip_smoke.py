"""Smoke run of the AdaptCL fleet simulator on a TPU, through ``run_simulation``.

    python chip_smoke.py              # one chip: phases A and B
    python chip_smoke.py --chips 4    # four chips: the mesh-sharded fleet only

Every phase trains ``VGG16_CIFAR`` at its full widths on seeded 32x32x3
synthetic data (weights from ``--seed``), ``adaptcl`` with W heterogeneous
workers (sigma=2, Non-IID s=80), 4 rounds with a pruned-rate learning event
every 2, so workers are really pruned.

* Phase A: the fused engine (dense compute), then the sequential reference
  engine on the same seed.
* Phase B: the masked engine with ``compute="block_skip"``: the convs and the
  head run through the ``pruned_matmul`` Pallas kernel, compiled by Mosaic.
* ``--chips 4``: the fused engine at W=12 with its stacks sharded over a
  4-device fleet mesh, against the same config on one chip.

Runs agree when their final test accuracies differ by at most
``ACC_TOL``.  All phases run in this one process, which holds the chip(s);
any failure raises and exits non-zero.  The last line of stdout is a JSON
object naming the device, printed only when every phase passed.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys

# Final test accuracy on the 512 seeded test images: runs of one config on
# different engines, compute paths or meshes must agree within this much
# (26 images).  The engines compute the same function, but the chip's default
# f32 matmul/conv precision and per-program algorithm choices perturb every
# step, and a near-tie in the pruning scores can flip a unit.
ACC_TOL = 0.05


def _device_line(jax):
    devs = jax.devices()
    d = devs[0]
    print(f"device: platform={d.platform} kind={d.device_kind} count={len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def _config(workers: int, seed: int, **kw):
    from repro.core.simulation import SimConfig
    from repro.core.timing import HeterogeneityConfig
    from repro.models.cnn import VGG16_CIFAR

    return SimConfig(
        method="adaptcl", rounds=4, prune_interval=2, num_workers=workers,
        noniid_s=80.0, het=HeterogeneityConfig(num_workers=workers, sigma=2.0),
        cnn=VGG16_CIFAR, seed=seed, **kw,
    )


def _require(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def _run(label: str, sim):
    import numpy as np

    from repro.core.simulation import run_simulation

    r = run_simulation(sim)
    steady = r.walltime_s - r.compile_walltime_s
    print(
        f"[{label}] engine={r.engine} compute={r.compute} W={sim.num_workers} "
        f"final_acc={r.final_acc:.6f} best_acc={r.best_acc:.6f} "
        f"prune_events={len(r.prune_events)} "
        f"retentions={[round(g, 4) for g in r.retentions]} "
        f"compile_walltime_s={r.compile_walltime_s:.3f} "
        f"steady_walltime_s={steady:.3f} walltime_s={r.walltime_s:.3f} "
        f"recompiles={r.recompiles} host_dispatches={r.host_dispatches} "
        f"n_devices={r.n_devices} "
        f"acc_per_round={[round(a, 6) for _, a in r.acc_time]}",
        flush=True,
    )
    finite = (
        all(math.isfinite(a) for _, a in r.acc_time)
        and math.isfinite(r.total_time)
        and all(np.isfinite(v).all() for v in r.global_params.values())
    )
    _require(finite, f"{label}: accuracies, clock and global params finite")
    return r


def _agree(label: str, a, b) -> None:
    d = abs(a.final_acc - b.final_acc)
    same_events = a.prune_events == b.prune_events
    gp = sum(
        float(((a.global_params[k] - b.global_params[k]) ** 2).sum())
        for k in a.global_params
    )
    gn = sum(float((v ** 2).sum()) for v in b.global_params.values())
    print(
        f"[{label}] final_acc delta={d:.6f} (tol {ACC_TOL}) "
        f"prune_events_identical={same_events} "
        f"global_params_rel_l2={math.sqrt(gp / gn):.3e}",
        flush=True,
    )
    _require(d <= ACC_TOL, f"{label}: final_acc within {ACC_TOL}")


def one_chip(seed: int) -> None:
    import jax

    # Phase A: the fused fast path, then the sequential reference engine
    fused = _run("A fused", _config(10, seed, engine="fused"))
    _require(len(fused.prune_events) > 0, "A fused: some worker was pruned")
    _require(min(fused.retentions) < 1.0, "A fused: some retention below 1")
    seq = _run("A sequential", _config(10, seed, engine="sequential"))
    _agree("A fused vs sequential", fused, seq)

    # Phase B: the block-skip Pallas kernel, compiled by Mosaic
    bs = _run("B block_skip", _config(
        10, seed, engine="masked", compute="block_skip",
        compute_blocks=(128, 128, 128),
    ))
    _require(not bs.compute_interpret, "B: kernel compiled, not interpreted")
    _require(len(bs.prune_events) > 0, "B: some worker was pruned")
    print(
        f"[B block_skip] flops_executed/flops_ideal="
        f"{bs.flops_executed / bs.flops_ideal:.6f} "
        f"blocks_per_image_final={bs.blocks_per_image_final:.1f} "
        f"process_peak_bytes_in_use="
        f"{(jax.devices()[0].memory_stats() or {}).get('peak_bytes_in_use')}",
        flush=True,
    )
    _agree("B block_skip vs A fused", bs, fused)


def four_chips(seed: int) -> None:
    from repro.launch.mesh import make_fleet_mesh

    sharded = _run("mesh fused", _config(
        12, seed, engine="fused", mesh=make_fleet_mesh(4),
    ))
    _require(sharded.n_devices == 4, "mesh: resident stacks span 4 devices")
    single = _run("one-chip fused", _config(12, seed, engine="fused"))
    _require(single.n_devices == 1, "one-chip: stacks on one device")
    _agree("mesh vs one-chip", sharded, single)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    device = _device_line(jax)
    if device["platform"] != "tpu":
        print("no TPU found: this smoke run measures nothing off the chip",
              file=sys.stderr)
        return 1
    if device["count"] < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices, "
              f"found {device['count']}", file=sys.stderr)
        return 1

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))
    from repro.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    warm = os.path.isdir(cache) and bool(os.listdir(cache))
    print(f"compile cache: {cache} (warm at start: {warm})", flush=True)
    if args.chips == 4:
        four_chips(args.seed)
    else:
        one_chip(args.seed)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
