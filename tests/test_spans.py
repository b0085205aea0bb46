"""The simulator's own tracing (core.spans, the device scopes of core.fused
and core.worker).

Pins down:
  * every host span of the fused engine lies under ``adaptcl.sim`` in a
    profiler trace, and ``SimResult.span_s`` agrees with the trace;
  * ``walltime_s``, ``compile_walltime_s`` and ``server_overhead_s`` are
    reads of the spans, on the fused and the per-round engines;
  * the compiled chunk program's op names carry the device scopes;
  * each simulation re-traces its programs (``compile.trace_lower``);
  * the accumulator counts nested compile events once and restores the
    outer one on exit.
"""
import glob
import os
import re

import jax
import numpy as np
import pytest

from repro.core import spans, worker
from repro.core.simulation import SimConfig, run_simulation
from repro.core.timing import HeterogeneityConfig
from repro.models.cnn import vgg_config

TINY = vgg_config("vgg_tiny_spans", [8, "M", 16], num_classes=4, image_size=8)

HOST_SPANS = ("setup", "chunk.plan", "chunk.dispatch", "chunk.readback",
              "chunk.account", "evaluate", "alg2", "first_call.fused",
              "first_call.eval_logits")


def _sim(engine="fused", **kw):
    base = dict(
        method="adaptcl", engine=engine, rounds=6, prune_interval=2,
        num_workers=4, batch_size=16, cnn=TINY,
        het=HeterogeneityConfig(num_workers=4, sigma=3.0), eval_every=2,
        seed=3,
    )
    base.update(kw)
    return SimConfig(**base)


def _host_spans(trace_dir):
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events if e.name.startswith(spans.PREFIX)]
    return out


def test_host_spans_nest_under_sim_and_match_the_trace(tmp_path):
    run_simulation(_sim())                      # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    result = run_simulation(_sim())
    jax.profiler.stop_trace()
    events = _host_spans(str(tmp_path))
    (sim,) = [e for e in events if e[0] == "adaptcl.sim"]
    seen = {}
    for name, start, end in events:
        assert sim[1] <= start <= end <= sim[2], name
        key = name[len(spans.PREFIX):]
        seen[key] = seen.get(key, 0.0) + (end - start) * 1e-9
    assert set(HOST_SPANS) <= set(seen)
    for key, secs in seen.items():
        assert result.span_s[key] == pytest.approx(secs, rel=0.05, abs=2e-5), key
    assert result.span_n["chunk.dispatch"] == result.fused_chunks == 3
    assert result.span_n["alg2"] == 3 and result.span_n["evaluate"] == 4


@pytest.mark.parametrize("engine", ["fused", "masked"])
def test_timers_are_reads_of_the_spans(engine):
    r = run_simulation(_sim(engine))
    s = r.span_s
    assert r.walltime_s == s["sim"]
    assert r.compile_walltime_s == pytest.approx(
        sum(v for k, v in s.items() if k.startswith("first_call.")))
    assert 0 < r.compile_walltime_s <= r.walltime_s
    assert r.server_overhead_s == pytest.approx(s.get("aggregate", 0.0) + s["alg2"])
    assert s["setup"] + s["evaluate"] <= s["sim"]
    if engine == "masked":                      # host aggregation, every round
        assert r.span_n["aggregate"] == 6
    else:
        assert "aggregate" not in s


def test_chunk_program_carries_device_scopes():
    call = worker.LocalTrainer._call_cached
    kept = {}

    def keep(self, sig, build, *args, **kw):
        out = call(self, sig, build, *args, **kw)
        if worker.sig_name(sig) == "fused" and "text" not in kept:
            kept["text"] = self._step_cache[sig][0].lower(*args).compile().as_text()
        return out

    worker.LocalTrainer._call_cached = keep
    try:
        run_simulation(_sim())
    finally:
        worker.LocalTrainer._call_cached = call
    names = set(re.findall(r'op_name="([^"]*)"', kept["text"]))
    for scope in ("broadcast", "train", "train.step", "train.gate", "prune", "aggregate"):
        # a path component, bare or under a transform: ``/train/``, ``vmap(train)``
        pat = re.compile(rf"(^|[/(]){re.escape(scope)}([/)]|$)")
        assert any(pat.search(n) for n in names), scope


def test_each_simulation_retraces_its_programs():
    run_simulation(_sim())
    again = run_simulation(_sim())
    assert again.span_s["compile.trace_lower"] > 0
    assert again.span_n["compile.trace_lower"] >= 1
    assert again.span_s["compile.load"] > 0


def test_sig_name():
    assert worker.sig_name(("eval_logits", (256, 8, 8, 3))) == "eval_logits"
    assert worker.sig_name(((("w", (3,)),), ("fused", 2, 3), 0.1)) == "fused"
    assert worker.sig_name(((("w", (3,)),), "grad", 0.0)) == "grad"
    assert worker.sig_name(((), (), 0.0)) == "call"


def test_nested_compile_events_count_once():
    acc = spans.Spans()
    # JAX reports a nested trace when it ends, before the one around it
    acc.add_interval("c", 2.5, 2.75, 1)
    acc.add_interval("c", 2.0, 3.0, 1)
    acc.add_interval("c", 1.0, 4.0, 1)
    acc.add_interval("c", 5.0, 6.0, 1)
    assert acc.seconds["c"] == pytest.approx(4.0)
    assert acc.counts["c"] == 2


def test_collect_restores_the_outer_accumulator():
    with spans.collect() as outer:
        with spans.span("a"):
            with spans.collect() as inner:
                with spans.span("b"):
                    np.ones(3).sum()
        with spans.span("c"):
            pass
    assert set(outer.seconds) == {"a", "c"} and set(inner.seconds) == {"b"}
    with spans.span("d"):                       # no simulation: counts nothing
        pass
    assert "d" not in outer.seconds


def test_persistent_cache_misses_are_counted():
    with spans.collect() as acc:
        jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
        jax.monitoring.record_event("/jax/some/other_event")
    assert acc.counts == {"compile.cache_miss": 1}
    assert acc.seconds == {"compile.cache_miss": 0.0}
