"""The change gaps of ``bench/compare.py`` on hand-made outcomes: per leaf,
and per layer with a layer's leaves taken together as one vector."""
import numpy as np
import pytest

from bench import compare
from bench.reference import Outcome


def _outcome(change: dict) -> Outcome:
    init = {k: np.zeros(n, np.float32) for k, (n, _) in change.items()}
    after = {k: np.full(n, v, np.float32) for k, (n, v) in change.items()}
    grads = {k: 1.0 for k in change}
    return Outcome(globals={0: init, 1: after}, accs=[0.0, 0.0], retentions=[1.0],
                   prune_events={}, first_grad_norms=grads)


REF = {"conv0/w": (100, 1.0), "conv0/bn_b": (4, 1.0), "conv1/w": (100, 1.0),
       "conv1/bn_b": (4, 1.0)}


def test_a_small_leaf_moves_the_leaf_gap_far_more_than_its_layer_gap():
    cand = dict(REF, **{"conv0/bn_b": (4, 3.0)})
    leaf = compare.leaf_gaps(_outcome(cand), _outcome(REF), 1)
    layer = compare.layer_gaps(_outcome(cand), _outcome(REF), 1)
    assert set(layer) == {"conv0", "conv1"}
    # leaf: |6 - 2| over the median leaf's norm (the mean of 2 and 10)
    assert leaf["conv0/bn_b"] == pytest.approx(4 / 6)
    assert layer["conv0"] == pytest.approx((np.sqrt(136) - np.sqrt(104)) / np.sqrt(104))
    assert layer["conv1"] == 0.0
    assert layer["conv0"] < leaf["conv0/bn_b"] / 4


def test_a_layer_that_moves_half_as_far_reads_one_half():
    cand = {k: (n, 0.5) for k, (n, _) in REF.items() if k.startswith("conv1")}
    cand.update({k: v for k, v in REF.items() if k.startswith("conv0")})
    nums = compare.numbers(_outcome(cand), _outcome(REF))
    assert nums["layer_change_gap_r1"] == pytest.approx(0.5)
    assert nums["change_gap_r1"] == pytest.approx(0.5)
