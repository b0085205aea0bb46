"""Run one benchmark cell of the AdaptCL fleet simulator on the chip.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is ``bench/workloads/<cell>.json`` (the simulation's settings) on the
configuration ``bench/configs/<config>.json`` (model, fleet, data).  The run:

1. set-up: starts JAX on the accelerator (exits non-zero without one, or with
   fewer chips than the cell asks for), makes the data from ``--seed``, and
   runs one simulation of the cell, which fills the persistent compile cache
   (``repro.compile_cache``: ``JAX_COMPILATION_CACHE_DIR`` when set, else
   ``<checkout>/.jax_cache``) and absorbs the process's first-run work;
2. window: back-to-back ``run_simulation`` calls of the same simulation until
   ``--seconds`` have passed; the one in flight is finished;
3. correctness: the set-up simulation is compared round by round with
   ``bench/reference.py`` run on the same inputs, and every simulation of the
   window with the set-up one.

The last line of standard output is one JSON object.  With ``--trace 0`` its
metrics are the cell's end-to-end metrics, with ``--trace 1`` (profiler on
during the window's first simulation) its per-layer metrics.  Each metric is read by
``bench/metrics/<name>.py``; the metrics a cell reports are those that
``BENCHMARK.json`` lists for it.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if sys.path and Path(sys.path[0] or ".").resolve() == BENCH:
    sys.path.pop(0)   # bench/trace.py must not shadow the standard library's trace


def _process_start() -> float:
    """Wall-clock time at which this process started (Linux), else now."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.time() - (uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return time.time()


T_START = _process_start()


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> tuple:
    wl = load_json(BENCH / "workloads" / f"{name}.json")
    cfg = load_json(BENCH / "configs" / f"{wl['config']}.json")
    return wl, cfg


def reader(metric: str) -> Callable:
    """``read`` of ``bench/metrics/<metric>.py``."""
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(cell: str, trace: bool) -> List[dict]:
    """Metrics ``BENCHMARK.json`` gives this cell: end-to-end or per-layer."""
    manifest = load_json(ROOT / "BENCHMARK.json")
    key = "per_layer" if trace else "end_to_end"
    return [m for m in manifest[key] if cell in m.get("workloads", [cell])]


def program_seed(seed: int) -> int:
    """The simulator's seed (it feeds 32-bit PRNG keys) from ``--seed``."""
    return seed % (2 ** 31 - 1)


def sim_settings(wl: dict, cfg: dict) -> dict:
    """What the reference needs of the cell besides the configuration."""
    s = wl["sim"]
    return {"method": s["method"], "sigma": s.get("sigma", 2.0), "beta": s.get("beta", 1.0),
            "importance": cfg["importance"]}


def build_sim(wl: dict, cfg: dict, task, seed: int):
    from repro.core.simulation import SimConfig
    from repro.core.timing import HeterogeneityConfig

    from bench.models import family

    m = cfg["model"]
    s = dict(wl["sim"])
    sigma = s.pop("sigma", 2.0)
    W = cfg["num_workers"]
    return SimConfig(
        rounds=cfg["rounds"], prune_interval=cfg["prune_interval"], num_workers=W,
        local_epochs=cfg["local_epochs"], batch_size=cfg["batch_size"], lr=cfg["lr"],
        lam=cfg["lam"], noniid_s=cfg["noniid_s"], importance=cfg["importance"],
        het=HeterogeneityConfig(num_workers=W, sigma=sigma), engine=wl["engine"],
        task=task, seed=seed, **family(m["kind"]).program_model(cfg["name"], m), **s,
    )


def make_inputs(cfg: dict, seed: int):
    """The cell's task from ``--seed``, the program's seed and the shard sizes."""
    from bench.data import partition_noniid
    from bench.models import family

    pseed = program_seed(seed)
    m = cfg["model"]
    W = cfg["num_workers"]
    task = family(m["kind"]).make_task(m, W * cfg["train_per_worker"], cfg["test_size"], seed)
    shard_sizes = [len(s) for s in partition_noniid(task.y_train, W, cfg["noniid_s"], pseed)]
    return task, pseed, shard_sizes


def run_captured(sim):
    """``run_simulation(sim)``, keeping the global model the program
    evaluates after each round (round 0 is the initial model).  The program
    returns only the final model, so this wraps ``simulation._env_accuracy``,
    which it calls once a round with the global parameters."""
    from repro.core import simulation

    captured: Dict[int, dict] = {}
    evaluate = simulation._env_accuracy

    def keep_global(env, params):
        captured[len(captured)] = {k: np.array(v, np.float32) for k, v in params.items()}
        return evaluate(env, params)

    simulation._env_accuracy = keep_global
    try:
        result = simulation.run_simulation(sim)
    finally:
        simulation._env_accuracy = evaluate
    return result, captured


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read."""
    cell: dict
    cfg: dict
    chips: int
    device_kind: str
    peaks: dict
    setup_s: float
    window_s: float
    sims: list                    # SimResult of each simulation in the window
    images: int                   # training images the window's simulations processed
    required_flops: float         # training FLOPs the retained sub-models need
    matmul_bytes: float           # least f32 operand and result bytes of those matmuls
    memory_peak_bytes: int
    trace: Optional[object] = None   # trace.Summary of the window's first simulation

    @property
    def rounds(self) -> int:
        return len(self.sims) * self.cfg["rounds"]

    @property
    def traced_rounds(self) -> int:
        return self.cfg["rounds"]


def builds(trainer, sig) -> bool:
    """Whether ``trainer._call_cached(sig, ...)`` builds its program: a
    signature new to the trainer that the process's table of built programs
    does not hold (the program counts ``program_cache.miss``).  A program
    found in the table is called warm, and its first call there is none."""
    from repro.core import worker

    return sig not in trainer._step_cache and (trainer._program_key, sig) not in worker._programs


def _annotate_program(jax) -> None:
    """Host spans around the program's calls, for labelling idle gaps: each
    jitted dispatch by its signature's first field, ``first_call`` where it
    builds the program, and each evaluation."""
    from repro.core import simulation, worker

    call = worker.LocalTrainer._call_cached

    def traced_call(self, sig, build, *args, **kw):
        name = sig[0] if isinstance(sig, tuple) and sig and isinstance(sig[0], str) else "call"
        first = builds(self, sig)
        with jax.profiler.TraceAnnotation(f"bench.{'first_call' if first else 'call'}.{name}"):
            return call(self, sig, build, *args, **kw)

    worker.LocalTrainer._call_cached = traced_call
    acc = simulation._env_accuracy

    def traced_acc(env, params):
        with jax.profiler.TraceAnnotation("bench.evaluate"):
            return acc(env, params)

    simulation._env_accuracy = traced_acc


def start_jax(chips: int, require_chip: bool = True):
    """JAX on the accelerator, with the persistent compile cache on; exits
    without one, or with fewer chips than the cell asks for.  Returns the
    devices and the table of peaks."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    import jax

    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < chips):
        raise SystemExit(
            f"needs {chips} TPU chip(s); JAX found {len(devs)} {devs[0].platform} device(s)")
    if devs[0].platform != "cpu":
        from repro.compile_cache import enable_compile_cache

        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    peaks = load_json(BENCH / "peaks.json")
    if require_chip and devs[0].device_kind not in peaks:
        raise SystemExit(f"device_kind {devs[0].device_kind!r} is not in bench/peaks.json")
    return devs, peaks


def measure(wl: dict, cfg: dict, seed: int, seconds: float, trace: bool,
            require_chip: bool = True) -> dict:
    """One run of the cell; returns the result object (see module doc).
    ``require_chip=False`` skips the look for an accelerator (CPU tests)."""
    chips = int(wl["chips"])
    devs, peaks = start_jax(chips, require_chip)
    import jax

    from bench import compare, flops, reference
    from repro.core.simulation import run_simulation

    kind = devs[0].device_kind
    task, pseed, shard_sizes = make_inputs(cfg, seed)
    settings = sim_settings(wl, cfg)
    # the set-up simulation is the one compared with the reference round by round
    sim = build_sim(wl, cfg, task, pseed)
    warm, captured = run_captured(sim)
    setup_s = time.time() - T_START
    print(f"engine: {wl['engine']} compute={warm.compute} devices={warm.n_devices} "
          f"setup_s={setup_s:.3f} warm_walltime_s={warm.walltime_s:.3f}", flush=True)

    misses = []
    jax.monitoring.register_event_listener(
        lambda event, **kw: misses.append(event)
        if event == "/jax/compilation_cache/cache_misses" else None)
    trace_dir = ROOT / ".bench_trace"
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        _annotate_program(jax)
        # host events: the harness's annotations only, no Python tracer
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
    sims = []
    stop_s = 0.0
    t0 = time.perf_counter()
    while True:
        # the traced run profiles the window's first simulation only, which
        # bounds the trace's size whatever the window holds
        traced = trace and not sims
        if traced:
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.window" if traced else "bench.untraced"):
            with jax.profiler.TraceAnnotation(f"bench.sim.{len(sims)}"):
                sims.append(run_simulation(sim))
        if traced:
            t_stop = time.perf_counter()
            jax.profiler.stop_trace()
            stop_s = time.perf_counter() - t_stop
        if time.perf_counter() - t0 >= seconds:
            break
    # writing the trace out is no work of the window's simulations
    window_s = time.perf_counter() - t0 - stop_s
    print(f"window: {len(sims)} simulations in {window_s:.3f} s (trace written in "
          f"{stop_s:.3f} s), {len(misses)} compile-cache misses", flush=True)
    summary = None
    if trace:
        from bench import trace as tr

        summary = tr.summarize(tr.read_events(str(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)
    peak = 0
    for d in jax.local_devices()[:chips]:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    work = [flops.sim_work(cfg, settings, r.prune_events, shard_sizes) for r in sims]
    run = Run(
        cell=wl, cfg=cfg, chips=chips, device_kind=kind, peaks=peaks.get(kind, {}),
        setup_s=setup_s, window_s=window_s, sims=sims,
        images=sum(w["images"] for w in work),
        required_flops=sum(w["flops"] for w in work),
        matmul_bytes=sum(w["bytes"] for w in work),
        memory_peak_bytes=peak, trace=summary,
    )
    metrics = {}
    for mdef in cell_metrics(wl["name"], trace):
        v = reader(mdef["name"])(run)
        if v is not None:
            metrics[mdef["name"]] = {"value": v, "unit": mdef["unit"]}

    # correctness: free the program's device state, then run the reference
    first = compare.from_program(warm, captured)
    repeats = [compare.repeat_gap(compare.from_program(r), first) for r in sims]
    del sims, run, warm
    captured.clear()
    gc.collect()
    t_ref = time.perf_counter()
    ref = reference.simulate(cfg, settings, task, pseed, log=_log,
                             train_rounds=compare.TRAIN_ROUNDS)
    ref_s = time.perf_counter() - t_ref
    limits = wl["limits"]
    readings = compare.numbers(first, ref)
    readings["repeat_gap"] = max(repeats)
    failed = int(any(readings[k] > lim for k, lim in limits.items() if k != "repeat_gap"))
    if "repeat_gap" in limits:
        failed += sum(r > limits["repeat_gap"] for r in repeats)
    compared = {k: {"value": readings[k], "limit": limits[k]} for k in limits}
    others = {k: v for k, v in readings.items() if k not in limits}
    _log(f"reference_s = {ref_s:.3f}; worst leaves: {compare.worst_leaves(first, ref)}")
    _log(f"not compared: {others}")
    for k, c in compared.items():
        _log(f"compared {k} = {c['value']!r} limit {c['limit']!r}")

    device = {"platform": devs[0].platform, "kind": kind, "count": chips,
              "memory_peak_bytes": peak}
    out = {"correct": failed == 0, "attempted": 1 + len(repeats), "failed": failed,
           "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = {"device_ops": [list(x) for x in summary.top_ops(10)],
                            "idle_gaps": [list(x) for x in summary.gaps[:10]]}
    out["readings"] = others
    out["compared"] = compared
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    wl, cfg = load_cell(args.workload)
    out = measure(wl, cfg, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
