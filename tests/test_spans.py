"""The simulator's own tracing (core.spans, the device scopes of core.fused
and core.worker).

Pins down:
  * every host span of the fused engine lies under ``adaptcl.sim`` in a
    profiler trace, and ``SimResult.span_s`` agrees with the trace;
  * ``walltime_s``, ``compile_walltime_s`` and ``server_overhead_s`` are
    reads of the spans, on the fused and the per-round engines;
  * the compiled chunk program's op names carry the device scopes;
  * a repeated simulation calls the programs the first one built, with no
    trace and no load, and gives the same results; a changed captured
    value (``lr``, a shape, the noise seed on the robust path) builds anew;
  * the table of built programs pins no device memory and drops its least
    recently used program first;
  * the accumulator counts nested compile events once and restores the
    outer one on exit.
"""
import gc
import glob
import os
import re
import weakref

import jax
import numpy as np
import pytest

from repro.core import spans, worker
from repro.core.aggregation import RobustAggConfig
from repro.core.simulation import SimConfig, run_simulation
from repro.core.timing import HeterogeneityConfig
from repro.models.cnn import vgg_config

TINY = vgg_config("vgg_tiny_spans", [8, "M", 16], num_classes=4, image_size=8)

HOST_SPANS = ("setup", "chunk.plan", "chunk.dispatch", "chunk.readback",
              "chunk.account", "evaluate", "alg2")


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


def _same_result(a, b):
    assert a.acc_time == b.acc_time
    assert a.global_params.keys() == b.global_params.keys()
    for k, v in a.global_params.items():
        np.testing.assert_array_equal(v, b.global_params[k], err_msg=k)


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
    # the programs of the first run are called warm: no first calls
    assert not [k for k in seen if k.startswith("first_call.")]
    for key, secs in seen.items():
        assert result.span_s[key] == pytest.approx(secs, rel=0.05, abs=2e-5), key
    assert result.span_n["chunk.dispatch"] == result.fused_chunks == 3
    assert result.span_n["alg2"] == 3 and result.span_n["evaluate"] == 4


@pytest.mark.parametrize("engine", ["fused", "masked"])
def test_timers_are_reads_of_the_spans(engine):
    worker.clear_program_cache()                # first calls to time
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


def test_a_repeated_simulation_reuses_its_programs():
    worker.clear_program_cache()
    first = run_simulation(_sim())
    again = run_simulation(_sim())
    assert first.span_n["program_cache.miss"] == 2        # fused, eval_logits
    assert first.span_s["compile.trace_lower"] > 0
    for key in ("compile.trace_lower", "compile.load", "program_cache.miss"):
        assert key not in again.span_n, key
    assert again.span_n["program_cache.hit"] == first.span_n["program_cache.miss"]
    assert not [k for k in again.span_s if k.startswith("first_call.")]
    assert again.compile_walltime_s == 0.0
    assert again.recompiles == first.recompiles == 1
    assert again.host_dispatches == first.host_dispatches
    _same_result(first, again)


@pytest.mark.parametrize("change", [{"lr": 0.02}, {"batch_size": 8}],
                         ids=["lr", "shape"])
def test_a_changed_captured_value_builds_anew(change):
    worker.clear_program_cache()
    run_simulation(_sim())
    changed = run_simulation(_sim(**change))
    assert changed.span_n["program_cache.miss"] >= 1
    assert changed.span_s["compile.trace_lower"] > 0
    assert changed.span_n["first_call.fused"] == 1
    worker.clear_program_cache()
    _same_result(changed, run_simulation(_sim(**change)))


@pytest.mark.parametrize("robust", [None, RobustAggConfig(clip=1.0)],
                         ids=["plain", "robust"])
def test_a_seed_reuses_the_chunk_unless_its_noise_is_compiled_in(robust):
    worker.clear_program_cache()
    run_simulation(_sim(robust=robust))
    other = run_simulation(_sim(robust=robust, seed=4))
    if robust is None:
        assert "program_cache.miss" not in other.span_n
        assert "first_call.fused" not in other.span_n
    else:
        assert other.span_n["program_cache.miss"] == 1
        assert other.span_n["first_call.fused"] == 1
    worker.clear_program_cache()
    _same_result(other, run_simulation(_sim(robust=robust, seed=4)))


def test_the_program_table_pins_no_device_memory():
    worker.clear_program_cache()
    gc.collect()
    before = len(jax.live_arrays())
    result = run_simulation(_sim())
    del result
    gc.collect()
    assert len(worker._programs) == 2
    assert len(jax.live_arrays()) == before


def test_the_program_table_evicts_its_least_recently_used(monkeypatch):
    monkeypatch.setattr(worker, "PROGRAM_CACHE_SIZE", 3)
    worker.clear_program_cache()
    run_simulation(_sim())                      # eval_logits, fused@16
    run_simulation(_sim(batch_size=8))          # eval_logits hits; fused@8
    run_simulation(_sim(batch_size=4))          # fused@4 evicts fused@16
    assert len(worker._programs) == 3
    again = run_simulation(_sim())
    assert again.span_n["program_cache.hit"] == 1          # eval_logits
    assert again.span_n["program_cache.miss"] == 1         # fused@16
    assert again.span_s["compile.trace_lower"] > 0
    assert len(worker._programs) == 3


def test_an_evicted_program_is_let_go(monkeypatch):
    monkeypatch.setattr(worker, "PROGRAM_CACHE_SIZE", 2)
    worker.clear_program_cache()
    run_simulation(_sim())                      # eval_logits, fused@16
    built = [weakref.ref(fn) for fn in worker._programs.values()]
    monkeypatch.setattr(worker, "PROGRAM_CACHE_SIZE", 1)
    # eval_logits hits, then fused@8 evicts both; its trainer, which the
    # table keeps through fused@8's closures, holds eval_logits no longer
    run_simulation(_sim(batch_size=8))
    gc.collect()
    assert [ref() for ref in built] == [None, None]


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
