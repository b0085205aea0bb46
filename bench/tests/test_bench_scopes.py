"""Device time per scope and idle time per span (``bench/scopes.py``), the
breakdown of a traced simulation (``bench/breakdown.py``) and the compile
readers, on traces built here and on real CPU traces at a tiny size."""
from types import SimpleNamespace

import pytest

from bench import scopes
from bench import trace as tr


def _op(name, start, dur, op_name="", module="jit_chunk", plane="/device:TPU:0"):
    return scopes.ScopedEvent(plane, "XLA Ops", name, float(start), float(dur),
                              module=module, op_name=op_name, opcode=scopes.opcode_of(name))


def _span(name, start, dur):
    return scopes.ScopedEvent("/host:CPU", "python", name, float(start), float(dur))


@pytest.mark.parametrize("op_name, scope", [
    ("jit(chunk)/while/body/vmap(train)/while/body/closed_call/train.step/"
     "transpose(jvp())/mul", "train.step"),
    ("jit(chunk)/while/body/cond/branch_1_fun/train/vmap(train)/while", "train"),
    ("jit(chunk)/while/body/cond/branch_1_fun/prune/mul", "prune"),
    ("jit(chunk)/while/body/aggregate/jit(_where)/select_n", "aggregate"),
    ("train.gate/jit(_where)/select_n", "train.gate"),
    ("jit(chunk)/while/body/dynamic_slice", None),
    ("", None),
])
def test_scope_of_reads_the_innermost_known_scope(op_name, scope):
    assert scopes.scope_of(op_name) == scope


def test_scope_s_counts_the_training_program_per_scope():
    events = [
        _op("%fusion.1 = f32[8] fusion(%a), kind=kLoop", 0, 100, "jit(chunk)/vmap(train)/train.step/mul"),
        _op("%fusion.2 = f32[8] fusion(%a), kind=kLoop", 100, 50, "jit(chunk)/vmap(train)/train.gate/select_n"),
        _op("%fusion.3 = f32[8] fusion(%a), kind=kLoop", 150, 30, "jit(chunk)/aggregate/add"),
        _op("%copy.4 = f32[8] copy(%a)", 180, 20, "jit(chunk)/while/body/dynamic_slice"),
        _op("%fusion.5 = f32[8] fusion(%a), kind=kLoop", 180, 20, ""),            # no op name
        _op("%while.6 = (s32[]) while(%t)", 0, 200, "jit(chunk)/while"),          # a container
        _op("%cond.9 = (f32[8]{0:T(128)}) conditional(%p), branch_computations={%a, %b}",
            0, 200, "jit(chunk)/while/body/cond"),                              # another one
        _op("%fusion.7 = f32[8] fusion(%a), kind=kLoop", 210, 40, "jit(f)/train/mul",
            module="jit__lambda"),                                              # another program
        _op("%fusion.8 = f32[8] fusion(%a), kind=kLoop", 950, 100, "jit(chunk)/prune/mul"),  # clipped
        _op("%fusion.1 = f32[8] fusion(%a), kind=kLoop", 0, 300, "jit(chunk)/vmap(train)/train.step/mul",
            plane="/device:TPU:1"),
    ]
    got = scopes.scope_s(events, (0, 1000))
    assert got == {
        "train.step": pytest.approx(200e-9),      # (100 + 300) / 2 chips
        "train.gate": pytest.approx(25e-9),
        "aggregate": pytest.approx(15e-9),
        "unscoped": pytest.approx(20e-9),
        "prune": pytest.approx(25e-9),
    }


def _idle_trace():
    """Spans and ops of a window [0, 1000] with idle [5, 500] and [600, 700]."""
    return [
        _span("bench.window", 0, 1000),
        _span("bench.sim.0", 1, 998),
        _span("adaptcl.sim", 10, 980),
        _span("adaptcl.chunk.account", 100, 200),
        _span("bench.evaluate", 290, 220),
        _span("adaptcl.evaluate", 300, 200),
        _span("adaptcl.chunk.dispatch", 590, 120),
        _span("bench.call.fused", 600, 100),
        _op("%a.1 = f32[8] fusion(%x), kind=kLoop", 0, 5),
        _op("%a.2 = f32[8] fusion(%x), kind=kLoop", 500, 100),
        _op("%a.3 = f32[8] fusion(%x), kind=kLoop", 700, 300),
    ]


def test_idle_by_span_cuts_idle_time_at_span_edges():
    events = _idle_trace()
    got = scopes.idle_by_span(events, (0, 1000))
    assert got == {
        "bench.sim.0": pytest.approx(5e-9),              # 5..10, outside the program
        "adaptcl.sim": pytest.approx(90e-9),             # 10..100
        "adaptcl.chunk.account": pytest.approx(200e-9),  # 100..300
        "adaptcl.evaluate": pytest.approx(200e-9),       # program span before harness span
        "adaptcl.chunk.dispatch": pytest.approx(100e-9),
    }
    s = tr.summarize(events)
    assert sum(got.values()) == pytest.approx(s.window_s - s.busy_s)


def test_idle_under_named_spans():
    events = _idle_trace()
    assert scopes.idle_under(events, (0, 1000), ("adaptcl.evaluate",)) == pytest.approx(200e-9)
    assert scopes.idle_under(events, (0, 1000), ("adaptcl.sim",)) == pytest.approx(590e-9)
    assert scopes.idle_under(events, (0, 1000), ("adaptcl.alg2",)) == 0.0


@pytest.mark.parametrize("name, opcode", [
    ("%cond.184 = (f32[10,64]{1,0:T(8,128)}, f32[2]{0}) conditional(pred[] %p), "
     "branch_computations={%a, %b}", "conditional"),
    ("%fusion.7 = f32[8]{0:T(128)S(1)} fusion(f32[8] %a), kind=kLoop, calls=%c", "fusion"),
    ("%copy-start.3 = (f32[512]{0:T(512)S(1)}, u32[]{:S(2)}) copy-start(f32[512] %b)", "copy-start"),
    ("fusion.7", ""),
])
def test_opcode_of_a_trace_name(name, opcode):
    assert scopes.opcode_of(name) == opcode


def test_instructions_without_metadata_take_their_users_op_name():
    text = """
  %p.0 = f32[4]{0} parameter(0)
  %copy-start.1 = (f32[4]{0}, f32[4]{0}, u32[]) copy-start(f32[4]{0} %p.0)
  %copy-done.1 = f32[4]{0} copy-done((f32[4]{0}, f32[4]{0}, u32[]) %copy-start.1)
  %fusion.2 = f32[4]{0} fusion(f32[4]{0} %copy-done.1), kind=kLoop, calls=%fc, metadata={op_name="jit(chunk)/train.step/mul" source_line=3}
  ROOT %reverse.3 = f32[4]{0} reverse(f32[4]{0} %fusion.2), dimensions={0}
"""
    names = scopes.op_names_from_hlo(text)
    assert names["fusion.2"] == "jit(chunk)/train.step/mul"
    assert names["copy-done.1"] == names["copy-start.1"] == names["fusion.2"]
    assert "reverse.3" not in names and "fc" not in names    # no user; a computation


def test_op_names_and_scopes_of_a_real_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    def chunk(x):
        with jax.named_scope("train"):
            y = jnp.tanh(x @ x)
        with jax.named_scope("aggregate"):
            return y.sum(0)

    f = jax.jit(chunk)
    x = jnp.ones((256, 256))
    names = scopes.op_names_from_hlo(f.lower(x).compile().as_text())
    assert any(scopes.scope_of(v) == "train" for v in names.values())
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    events = scopes.read_events(str(tmp_path), {"jit_chunk": names})
    win = next(e for e in tr.host_spans(events) if e.name == "bench.window")
    got = scopes.scope_s(events, (win.start_ns, win.end_ns))
    assert got.get("train", 0) > 0 and got.get("aggregate", 0) > 0
    assert tr.summarize(events).busy_s > 0          # the harness's reduction reads them too


def _tiny(cell):
    from bench import run

    wl, cfg = run.load_cell(cell)
    cfg = dict(cfg, num_workers=4, train_per_worker=64, test_size=300, rounds=6)
    if cfg["model"]["kind"] == "vgg":
        cfg["model"] = dict(cfg["model"], plan=[8, 8, "M", 16, "M", 16, "M"], image_size=8)
    else:
        cfg["model"] = dict(cfg["model"], stem=8, stages=[[2, 8], [1, 16]], image_size=8)
    return wl, cfg


@pytest.fixture
def restore_program(monkeypatch):
    """The traced paths wrap two functions of the program; put them back."""
    from repro.core import simulation, worker

    monkeypatch.setattr(worker.LocalTrainer, "_call_cached", worker.LocalTrainer._call_cached)
    monkeypatch.setattr(simulation, "_env_accuracy", simulation._env_accuracy)


def test_breakdown_of_a_tiny_traced_simulation(tmp_path, restore_program):
    from bench import breakdown

    wl, cfg = _tiny("resnet20-w10-sync")
    out = breakdown.profile_cell(wl, cfg, 2 ** 31 + 977, tmp_path / "trace", require_chip=False)
    scoped = {k: v for k, v in out["scope_s"].items() if k != "unscoped"}
    assert {"broadcast", "train.step", "aggregate", "prune"} <= set(scoped)
    assert sum(scoped.values()) >= 0.9 * sum(out["scope_s"].values())
    idle = out["idle_by_span"]
    assert sum(idle.values()) == pytest.approx(out["window_s"] - out["busy_s"], rel=1e-6)
    named = sum(v for k, v in idle.items() if k != "adaptcl.sim")
    assert named >= 0.9 * sum(idle.values())
    m = out["metrics"]
    assert m["local_train_ms_per_round"] > 0 and m["prune_ms_per_round"] > 0
    assert m["trace_lower_s_per_sim"] > 0 and m["idle_eval_ms_per_round"] > 0
    assert out["span_n"]["chunk.dispatch"] == 3


def test_traced_run_reports_the_compile_counters(restore_program):
    from bench import run

    wl, cfg = _tiny("vgg16-w10-sync")
    out = run.measure(wl, cfg, 2 ** 31 + 977, 0.1, True, require_chip=False)
    assert out["correct"], out
    assert out["metrics"]["trace_lower_s_per_sim"]["value"] > 0
    assert out["metrics"]["cache_load_s_per_sim"]["value"] > 0


@pytest.mark.parametrize("metric", ["trace_lower_s_per_sim", "cache_load_s_per_sim"])
def test_compile_readers(metric):
    from bench import run

    read = run.reader(metric)
    key = {"trace_lower_s_per_sim": "compile.trace_lower",
           "cache_load_s_per_sim": "compile.load"}[metric]
    sims = [SimpleNamespace(span_s={key: 2.0}), SimpleNamespace(span_s={key: 4.0})]
    assert read(SimpleNamespace(sims=sims)) == pytest.approx(3.0)
    # a program that keeps no spans (an older one) reports nothing
    assert read(SimpleNamespace(sims=[SimpleNamespace()])) is None


def test_an_op_belongs_to_the_program_run_it_starts_in():
    # a TPU trace names no program on its operations: the XLA Modules line does
    runs = [(0.0, 10.0, "jit_chunk"), (20.0, 30.0, "jit__lambda")]
    got = [scopes._run_at(runs, t) for t in (-1, 0, 9.5, 10, 15, 20, 29, 30)]
    assert got == ["", "jit_chunk", "jit_chunk", "", "", "jit__lambda", "jit__lambda", ""]
