"""The trace reduction on a trace built here and on a real CPU trace."""
import pytest

from bench import trace as tr


def _ev(name, start, dur, plane="/device:TPU:0", line="XLA Ops", **stats):
    return tr.Event(plane, line, name, float(start), float(dur), tuple(stats.items()))


def _span(name, start, dur):
    return tr.Event("/host:CPU", "python", name, float(start), float(dur))


def test_union_merges_overlaps():
    assert tr.union_ns([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_summarize_busy_idle_classes_and_gaps():
    events = [
        _span("bench.window", 0, 1000),
        _span("bench.sim.0", 0, 600),
        _span("bench.evaluate", 400, 150),
        _ev("%convolution.3 = f32[8] convolution(f32[8] %all-reduce.4)", 100, 200),
        _ev("%fusion.7 = f32[8] fusion(f32[8] %convolution.3), kind=kOutput", 250, 100),
        _ev("%closed_call.2 = f32[8] fusion(f32[8] %x), kind=kCustom, calls=%c", 600, 100),
        _ev("%all-reduce.1 = f32[8] all-reduce(f32[8] %fusion.7)", 800, 50),
        _ev("%fusion.9 = f32[2] fusion(f32[2] %x), kind=kLoop", 900, 200),  # past the window
        _ev("%while.1 = (s32[]) while(%tuple)", 100, 900),             # spans its body
        _ev("%fusion.4 = f32[8] fusion(f32[8] %a), kind=kOutput, calls=%c", 860, 20),
        _ev("jit_chunk(123)", 100, 850, line="XLA Modules"),
        _ev("jit__lambda(456)", 980, 50, line="XLA Modules"),
        _ev("outside", 2000, 10),
    ]
    s = tr.summarize(events)
    assert s.window_s == pytest.approx(1e-6)
    assert s.chips == 1
    # busy: the while op covers [100, 1000]
    assert s.busy_s == pytest.approx(900e-9)
    assert s.idle_share == pytest.approx(0.1)
    # classes read the op itself, never its operands
    assert s.class_s == {"pruned_matmul": pytest.approx(100e-9),
                         "collective": pytest.approx(50e-9)}
    assert s.op_s["fusion.9"] == pytest.approx(100e-9)        # clipped at the window
    assert s.op_s["closed_call.2 [pruned_matmul]"] == pytest.approx(100e-9)
    assert "while.1" not in s.op_s
    assert s.module_s == {"jit_chunk": pytest.approx(850e-9), "jit__lambda": pytest.approx(20e-9)}
    assert s.gaps == [("bench.sim.0", pytest.approx(100e-9))]


def test_gaps_are_labelled_by_the_innermost_span():
    events = [
        _span("bench.window", 0, 1000),
        _span("bench.sim.0", 0, 600),
        _span("bench.evaluate", 400, 150),
        _ev("%convolution.3 = f32[8] convolution(f32[8] %x)", 100, 250),
        _ev("%custom-call.2 = f32[8] custom-call(f32[8] %x), custom_call_target=\"tpu_custom_call\"", 600, 100),
        _ev("%all-reduce.1 = f32[8] all-reduce(f32[8] %y)", 800, 50),
        _ev("%fusion.9 = f32[8] fusion(f32[8] %z), kind=kLoop", 900, 200),
    ]
    s = tr.summarize(events)
    # busy: [100,350] + [600,700] + [800,850] + [900,1000] = 250+100+50+100
    assert s.busy_s == pytest.approx(500e-9)
    got = sorted((round(g * 1e9), lbl) for lbl, g in s.gaps)
    assert got == [(50, "bench.window"),                      # 850..900
                   (100, "bench.sim.0"),                      # 0..100
                   (100, "bench.window"),                     # 700..800
                   (250, "bench.evaluate")]                   # 350..600
    assert s.gaps[0][1] == pytest.approx(250e-9)


def test_summarize_averages_over_chips():
    events = [_span("bench.window", 0, 100),
              _ev("%all-reduce.1 = f32[8] all-reduce(f32[8] %x)", 0, 40, plane="/device:TPU:0"),
              _ev("%all-reduce.1 = f32[8] all-reduce(f32[8] %x)", 0, 80, plane="/device:TPU:1")]
    s = tr.summarize(events)
    assert s.chips == 2
    assert s.busy_s == pytest.approx(60e-9)
    assert s.class_s["collective"] == pytest.approx(60e-9)


def test_no_device_op_is_an_error():
    with pytest.raises(ValueError):
        tr.summarize([_span("bench.window", 0, 10)])


def test_real_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    events = tr.read_events(str(tmp_path))
    assert any(e.name == "bench.window" for e in tr.host_spans(events))
    s = tr.summarize(events)
    assert 0 < s.busy_s <= s.window_s
    assert 0 <= s.idle_share < 1
