"""The benchmark's FLOP and byte counts against the simulator's own."""
import numpy as np
import pytest

from bench import flops
from bench.models import family

VGG = {"kind": "vgg", "plan": [8, 8, "M", 16, "M", 24, "M"], "num_classes": 10, "image_size": 16}
RES = {"kind": "resnet", "stem": 8, "stages": [[2, 8], [2, 16], [1, 32]], "num_classes": 10,
       "image_size": 16}


def _program_cfg(model):
    return family(model["kind"]).program_model("t", model)["cnn"]


@pytest.mark.parametrize("model", [VGG, RES], ids=["vgg", "resnet"])
def test_forward_flops_match_program_at_random_widths(model):
    import jax

    from repro.core.aggregation import subparam_shapes
    from repro.models.cnn import build_unit_space, cnn_flops_from_shapes, init_cnn

    cfg = _program_cfg(model)
    params = init_cnn(jax.random.PRNGKey(0), cfg)
    space, unit_map = build_unit_space(cfg, params)
    base = {k: v.shape for k, v in params.items()}
    units = family(model["kind"]).units(model)
    assert [(l.name, l.num_units) for l in space.layers] == [(n, c) for n, c, _ in units]
    owned = {}
    for name, _, owns in units:
        for leaf, axis in owns:
            owned.setdefault(leaf, []).append((name, axis))
    assert {k: sorted(v) for k, v in owned.items()} == {k: sorted(v) for k, v in unit_map.items()}
    rng = np.random.default_rng(0)
    for _ in range(5):
        index = {l.name: np.sort(rng.choice(l.num_units, rng.integers(2, l.num_units + 1),
                                            replace=False)) for l in space.layers}
        kept = {k: len(v) for k, v in index.items()}
        want = cnn_flops_from_shapes(subparam_shapes(index, unit_map, base), cfg)
        assert flops.forward_flops(model, kept) == pytest.approx(want, rel=1e-12)
    assert flops.forward_flops(model) == pytest.approx(cnn_flops_from_shapes(base, cfg))


def test_step_bytes_counts_three_matmuls_per_layer():
    model = {"kind": "vgg", "plan": [4], "num_classes": 2, "image_size": 2}
    # conv: M=4*b, K=27, N=4; head: M=b, K=4, N=2
    b = 3
    want = 3 * 4 * ((12 * 27 + 27 * 4 + 12 * 4) + (3 * 4 + 4 * 2 + 3 * 2))
    assert flops.step_bytes(model, None, b) == want


def test_sim_work_follows_prune_events():
    cfg = {"model": VGG, "batch_size": 32, "local_epochs": 1.0, "rounds": 3}
    kept = {"conv0": 4, "conv1": 8, "conv2": 16, "conv3": 24}
    events = [(2, 1, {k: list(range(n)) for k, n in kept.items()})]
    w = flops.sim_work(cfg, {"beta": 1.0}, events, [100, 64])
    assert w["images"] == 3 * (128 + 64)
    full, pruned = 3 * flops.forward_flops(VGG), 3 * flops.forward_flops(VGG, kept)
    # worker 1 trains rounds 1-2 at full width, round 3 pruned
    assert w["flops"] == pytest.approx(3 * 128 * full + 2 * 64 * full + 64 * pruned)
    half = flops.sim_work(cfg, {"beta": 0.5}, events, [100, 64])
    assert half["images"] == 3 * 128 + 64 * 3
    assert half["flops"] == pytest.approx(3 * 128 * full + 64 * full + 32 * full + 32 * pruned
                                          + 64 * pruned)
