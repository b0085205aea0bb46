"""The seam between the harness and the model families
(``bench/models/<kind>.py``): moving the CNN code behind it changed no
number, a family is added by files alone, and an unknown kind is refused.

``models_recorded.json`` holds numbers recorded with the harness of commit
7e2d89b955857a36a00803250f8f0d370c6b4219, the last before the seam."""
import json
import shutil
from pathlib import Path

import numpy as np
import pytest

from bench import compare, flops, models, run
from bench.reference import Units
from bench.tests.cells import tiny

RECORDED = json.loads((Path(__file__).parent / "models_recorded.json").read_text())
CELLS = ["vgg16-w10-sync", "resnet20-w10-sync"]
SEED = 2 ** 31 + 977
CONFIGS = sorted(p.stem for p in (run.BENCH / "configs").glob("*.json"))


def _prune_events(model):
    """A fixed schedule: three workers prune in round 3, two prune again in
    round 5, each keeping a leading share of every layer's units."""
    units = models.family(model["kind"]).units(model)
    events = [(3, w, {l: list(range(n - (w + 1) * n // 32)) for l, n, _ in units})
              for w in (1, 4, 7)]
    return events + [(5, w, {l: list(range(n - (w + 2) * n // 16)) for l, n, _ in units})
                     for w in (1, 2)]


@pytest.mark.parametrize("beta", [1.0, 0.5])
@pytest.mark.parametrize("cell", CELLS)
def test_sim_work_at_full_size_is_the_recorded(cell, beta):
    # host integer and float arithmetic in the same order: equal exactly
    wl, cfg = run.load_cell(cell)
    settings = dict(run.sim_settings(wl, cfg), beta=beta)
    got = flops.sim_work(cfg, settings, _prune_events(cfg["model"]), [5000] * 10)
    assert got == RECORDED["sim_work"][f"{cell}/{beta}"]


@pytest.mark.parametrize("cell", CELLS)
def test_tiny_reference_is_the_recorded(cell):
    from bench import reference

    wl, cfg = tiny(cell)
    run.start_jax(1, require_chip=False)
    task, pseed, _ = run.make_inputs(cfg, SEED)
    ref = reference.simulate(cfg, run.sim_settings(wl, cfg), task, pseed,
                             train_rounds=compare.TRAIN_ROUNDS)
    want = RECORDED["tiny_reference"][cell]
    # The same float32 operations in the same order on one CPU build give the
    # same bits; rtol 1e-6 leaves room only for a change of the build's
    # threading, which moved these numbers by up to 3.5e-7 after round 2.
    g = ref.globals[2]
    assert sorted(g) == sorted(want["round2"])
    for leaf, (norm, weighted) in want["round2"].items():
        a = np.asarray(g[leaf], np.float64).ravel()
        w = np.linspace(1.0, 2.0, a.size)       # a weighted norm sees a permutation
        assert np.sqrt((a * a).sum()) == pytest.approx(norm, rel=1e-6), leaf
        assert np.sqrt((a * a * w).sum()) == pytest.approx(weighted, rel=1e-6), leaf
    # the schedule and the prunes read no trained value: exact
    assert ref.retentions == want["retentions"]
    assert {f"{t},{w}": {l: len(ids) for l, ids in idx.items()}
            for (t, w), idx in ref.prune_events.items()} == want["prune_events"]


@pytest.mark.parametrize("config", CONFIGS)
def test_units_own_slices_of_the_initial_model(config):
    model = json.loads((run.BENCH / "configs" / f"{config}.json").read_text())["model"]
    fam = models.family(model["kind"])
    model = fam.tiny(model)
    p = fam.init_params(model, 0)
    shapes = {k: tuple(v.shape) for k, v in p.items()}
    space = fam.units(model)
    for layer, count, owns in space:
        for leaf, axis in owns:
            assert shapes[leaf][axis] == count, (layer, leaf)
        assert fam.unit_scale(p, layer).shape == (count,)
    units = Units(space, shapes)
    assert units.retention(units.full()) == 1.0


def test_a_family_is_added_by_files_alone(tmp_path, monkeypatch):
    wl, cfg = tiny("resnet20-w10-sync")
    cfg["model"] = dict(cfg["model"], kind="resnet_copy")
    shutil.copy(models.DIR / "resnet.py", tmp_path / "resnet_copy.py")
    monkeypatch.setattr(models, "DIR", tmp_path)       # the lookup sees the copy alone
    assert Path(models.family("resnet_copy").__file__).parent == tmp_path
    out = run.measure(wl, cfg, SEED, 0.1, False, require_chip=False)
    assert out["correct"], out


def test_an_unknown_kind_names_the_file_it_looked_for():
    with pytest.raises(SystemExit, match=r"unknown model kind 'transformer': no file .*"
                                         r"bench/models/transformer\.py"):
        models.family("transformer")
    with pytest.raises(SystemExit, match="unknown model kind"):
        models.family("../run")
