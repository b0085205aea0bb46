"""Profile one simulation of a cell and break its time down by the
program's own host spans and device scopes.

    python bench/breakdown.py --workload <cell> --seed <n> [--out results]

The set-up and the traced simulation are those of ``bench/run.py --trace
1``: a set-up simulation, then one simulation under the profiler with the
same options and the harness's own spans; no reference is run.  The set-up
simulation, on an emptied table of built programs, builds the training
program and keeps it with its arguments; after the trace a fresh compile
of it gives the HLO text that names each device operation's scope, which a
TPU trace does not carry (``compiled_texts``).  Prints one
JSON line and writes it, with the training program's device seconds per
operation (``chunk_op_s``), to ``<out>/breakdown-<cell>-<seed>.json``, and
the HLO text beside it (``.hlo.txt.gz``):

* ``window_s``, ``busy_s``, ``idle_share``, ``module_s``: as the harness's
  reduction (``bench/trace.py``) reads them;
* ``scope_s``: device seconds per scope of the training program
  (``bench/scopes.py``), ``scope_top_ops``, its longest operations, and
  ``unnamed_op_s``, the seconds of operations the HLO text names no scope
  for;
* ``idle_by_span``: idle seconds of the chip per innermost span;
* ``metrics``: the per-round and per-simulation numbers read off these;
* ``span_s``, ``span_n``: the traced simulation's own spans
  (``SimResult``).
"""
from __future__ import annotations

import argparse
import contextlib
import gzip
import json
import re
import shutil
import sys
from pathlib import Path
from types import SimpleNamespace
from typing import Dict, Iterable, List

BENCH = Path(__file__).resolve().parent
if sys.path and Path(sys.path[0] or ".").resolve() == BENCH:
    sys.path[0] = str(BENCH.parent)   # bench/trace.py must not shadow the standard library's trace

from bench import run  # noqa: E402

BOUNDARY = ("adaptcl.chunk.plan", "adaptcl.chunk.account", "adaptcl.alg2")


@contextlib.contextmanager
def first_calls(names: Iterable[str]):
    """The jitted function and arguments of each program built (``run.builds``)
    whose jit-cache signature is named in ``names`` (``worker.sig_name``)."""
    from repro.core import worker

    call = worker.LocalTrainer._call_cached
    kept: List[tuple] = []

    def keep(self, sig, build, *args, **kw):
        first = run.builds(self, sig)
        out = call(self, sig, build, *args, **kw)
        if first and worker.sig_name(sig) in names:
            kept.append((self._step_cache[sig][0], args))
        return out

    worker.LocalTrainer._call_cached = keep
    try:
        yield kept
    finally:
        worker.LocalTrainer._call_cached = call


def compiled_texts(calls) -> Dict[str, str]:
    """HLO text, by module name, of a fresh compile of each ``(function,
    arguments)``.  A fresh compile, because an executable served by the
    persistent cache may come from a compile of the same program with other
    (or no) op-name metadata: the cache's key leaves metadata out.  The
    compiler is deterministic, so the instruction names are those of the
    executable that ran."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was_on = jax.config.jax_enable_compilation_cache
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        texts = [fn.lower(*args).compile().as_text() for fn, args in calls]
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()
    return {re.match(r"HloModule ([^,\s]+)", t).group(1): t for t in texts}


def profile_cell(wl: dict, cfg: dict, seed: int, trace_dir: Path,
                 require_chip: bool = True) -> dict:
    """The breakdown of one traced simulation of the cell (module doc)."""
    devs, _ = run.start_jax(int(wl["chips"]), require_chip)
    import jax

    from bench import scopes
    from bench import trace as tr
    from repro.core.simulation import run_simulation

    from repro.core import worker

    task, pseed, _ = run.make_inputs(cfg, seed)
    sim = run.build_sim(wl, cfg, task, pseed)
    # the set-up simulation builds every program, so that its build of the
    # training chunk is kept for the HLO text
    worker.clear_program_cache()
    with first_calls(("fused",)) as calls:
        run_simulation(sim)
    run._annotate_program(jax)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.sim.0"):
            result = run_simulation(sim)
    jax.profiler.stop_trace()

    texts = compiled_texts(calls)
    del calls
    op_names = {m: scopes.op_names_from_hlo(t) for m, t in texts.items()}
    events = scopes.read_events(str(trace_dir), op_names)
    summary = tr.summarize(events)
    win = next(e for e in tr.host_spans(events) if e.name == "bench.window")
    window = (win.start_ns, win.end_ns)
    scope_s = scopes.scope_s(events, window)
    idle = scopes.idle_by_span(events, window)
    chunk_op_s: Dict[str, float] = {}
    op_scope: Dict[str, str] = {}
    unnamed = 0.0
    dev = tr.device_ops(events)
    for e in dev[sorted(dev)[0]]:
        if ("chunk" in e.module and not tr.is_container(e)
                and e.opcode not in scopes.CONTAINER_OPCODES):
            name = tr.short_name(e)
            chunk_op_s[name] = chunk_op_s.get(name, 0.0) + e.dur_ns * 1e-9
            op_scope[name] = scopes.scope_of(e.op_name) or "unscoped"
            unnamed += e.dur_ns * 1e-9 * (not e.op_name)
    top: Dict[str, list] = {}
    for name, secs in sorted(chunk_op_s.items(), key=lambda kv: -kv[1]):
        ops = top.setdefault(op_scope[name], [])
        if len(ops) < 8:
            ops.append((name, secs))
    rounds = cfg["rounds"]

    def per_round_ms(*names):
        return 1e3 * sum(v for k, v in scope_s.items()
                         if k in names or k.split(".")[0] in names) / rounds

    # the harness's readers where the benchmark has the metric
    like_run = SimpleNamespace(trace=summary, sims=[result], rounds=rounds, traced_rounds=rounds)
    metrics = {name: run.reader(name)(like_run) for name in (
        "train_ms_per_round", "first_call_s_per_sim", "trace_lower_s_per_sim",
        "cache_load_s_per_sim", "host_alg2_ms_per_round", "host_dispatches_per_round")}
    metrics.update({
        "local_train_ms_per_round": per_round_ms("train"),
        "aggregate_ms_per_round": per_round_ms("aggregate"),
        "prune_ms_per_round": per_round_ms("importance", "prune"),
        "idle_eval_ms_per_round":
            1e3 * scopes.idle_under(events, window, ("adaptcl.evaluate",)) / rounds,
        "idle_boundary_ms_per_round":
            1e3 * scopes.idle_under(events, window, BOUNDARY) / rounds,
    })
    out = {
        "cell": wl["name"], "seed": seed, "device": devs[0].device_kind,
        "window_s": summary.window_s, "busy_s": summary.busy_s,
        "idle_share": summary.idle_share, "module_s": summary.module_s,
        "scope_s": scope_s, "idle_by_span": idle, "metrics": metrics,
        "unnamed_op_s": unnamed,
        "scope_top_ops": top,
        "span_s": result.span_s, "span_n": result.span_n,
    }
    shutil.rmtree(trace_dir, ignore_errors=True)
    out["chunk_op_s"] = chunk_op_s
    out["hlo"] = texts
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", type=Path, default=run.ROOT / "results",
                    help="directory for the breakdown and the HLO text")
    args = ap.parse_args(argv)
    wl, cfg = run.load_cell(args.workload)
    out = profile_cell(wl, cfg, args.seed, run.ROOT / ".bench_trace")
    dest = args.out / f"breakdown-{args.workload}-{args.seed}"
    dest.parent.mkdir(parents=True, exist_ok=True)
    with gzip.open(f"{dest}.hlo.txt.gz", "wt") as f:
        f.write("\n".join(out.pop("hlo").values()))
    Path(f"{dest}.json").write_text(json.dumps(out, indent=1))
    out.pop("chunk_op_s")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
