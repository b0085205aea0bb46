"""The numbers that decide ``correct``: the set-up simulation against the
reference on the same inputs, and every simulation of the window against the
set-up simulation.

The reference trains the fleet for the first ``TRAIN_ROUNDS`` rounds (the
server's schedule, which with index-order importance needs no model, runs
on to the end).  With the learning events every 2 rounds, round 3 ends with
the first prune and aggregates the pruned workers' models, and round 4 is
the first in which pruned workers train their masked sub-models.  Against
the reference, with ``K = TRAIN_ROUNDS``:

* ``change_gap_r1``, ``change_gap_rK``: for each parameter leaf, the gap
  between the norm of the global model's change since its initialisation
  after round 1 (round K) in the simulation and in the reference, over the
  reference's norm for that leaf or for the median leaf, whichever is
  larger; the worst leaf counts.  Leaves whose first gradient in the
  reference is under a thousandth of the median leaf's are left out.
* ``median_change_gap_r1``, ``median_change_gap_rK``: the same gaps, the
  median leaf's.
* ``layer_change_gap_r1``, ``layer_change_gap_rK``: the same gap taken per
  layer (the leaves that share a name up to the last ``/``: for the CNNs a
  convolution or the head with its BN scale and shift), over the
  reference's norm for that layer or for the median layer, whichever is
  larger; the worst layer counts.
* ``kept_units_mismatch``: over all prune events of the simulation, the
  largest share of a model's prunable units whose kept or pruned status
  differs (1 where the two sides pruned different workers or rounds).
* ``retention_gap``: the largest gap between a worker's final retention.
* ``acc_gap``: the largest gap between the test accuracies after the
  trained rounds.
* ``init_gap``: the largest relative gap between the two initial models.

Against the set-up simulation, ``repeat_gap``: the largest absolute gap of
any final global parameter of a window's simulation, relative to the
largest magnitude of that leaf (the same program on the same inputs).
"""
from __future__ import annotations

from typing import Dict

import numpy as np

from bench.reference import Outcome

GRAD_FLOOR = 1e-3
TRAIN_ROUNDS = 4


def from_program(result, globals_by_round=None) -> Outcome:
    """Outcome of a ``SimResult`` of the program, with the global model of
    every round where the caller captured it."""
    final = {k: np.asarray(v, np.float32) for k, v in result.global_params.items()}
    return Outcome(
        globals=dict(globals_by_round or {}) or {-1: final},
        accs=[float(a) for _, a in result.acc_time],
        retentions=[float(g) for g in result.retentions],
        prune_events={(int(t), int(w)): {l: np.asarray(ids, np.int64) for l, ids in idx.items()}
                      for t, w, idx in result.prune_events},
    )


def final_params(o: Outcome) -> Dict[str, np.ndarray]:
    return o.globals[max(o.globals)]


def counted_leaves(ref: Outcome):
    g = ref.first_grad_norms
    floor = GRAD_FLOOR * float(np.median(list(g.values())))
    return sorted(k for k, v in g.items() if v >= floor)


def change_norms(cand: Outcome, ref: Outcome, t: int) -> Dict[str, tuple]:
    """Per counted leaf: the norms of the change since the initial model
    after round ``t``, the candidate's and the reference's."""
    init = ref.globals[0]
    return {k: (float(np.linalg.norm(cand.globals[t][k] - init[k])),
                float(np.linalg.norm(ref.globals[t][k] - init[k])))
            for k in counted_leaves(ref)}


def _gaps(norms: Dict[str, tuple]) -> Dict[str, float]:
    med = float(np.median([r for _, r in norms.values()]))
    return {k: abs(c - r) / max(r, med) for k, (c, r) in norms.items()}


def leaf_gaps(cand: Outcome, ref: Outcome, t: int) -> Dict[str, float]:
    """Per counted leaf: the gap of the norms of the change since the
    initial model after round ``t``, over the reference's norm of that leaf
    or of the median leaf, whichever is larger."""
    return _gaps(change_norms(cand, ref, t))


def layer_gaps(cand: Outcome, ref: Outcome, t: int) -> Dict[str, float]:
    """As ``leaf_gaps``, per layer: the counted leaves that share a name up
    to the last ``/`` taken together as one vector."""
    sq: Dict[str, list] = {}
    for k, (c, r) in change_norms(cand, ref, t).items():
        acc = sq.setdefault(k.rsplit("/", 1)[0], [0.0, 0.0])
        acc[0] += c * c
        acc[1] += r * r
    return _gaps({k: (float(np.sqrt(c)), float(np.sqrt(r))) for k, (c, r) in sq.items()})


def compared_rounds(cand: Outcome, ref: Outcome) -> list:
    """Round 1 and the last round whose global model both sides kept."""
    return sorted({1, max(t for t in ref.globals if t in cand.globals)})


def worst_leaves(cand: Outcome, ref: Outcome, n: int = 3) -> Dict[str, list]:
    """The ``n`` worst leaves of each change gap, with their gaps."""
    out = {}
    for t in compared_rounds(cand, ref):
        for name, g in (("change_gap", leaf_gaps(cand, ref, t)),
                        ("layer_change_gap", layer_gaps(cand, ref, t))):
            out[f"{name}_r{t}"] = [(k, round(g[k], 5))
                                   for k in sorted(g, key=g.get, reverse=True)[:n]]
    return out


def kept_units_mismatch(cand: Outcome, ref: Outcome) -> float:
    if set(cand.prune_events) != set(ref.prune_events):
        return 1.0
    worst = 0.0
    for key, ridx in ref.prune_events.items():
        cidx = cand.prune_events[key]
        total = diff = 0
        for layer, r in ridx.items():
            c = cidx.get(layer, np.zeros(0, np.int64))
            diff += len(set(map(int, r)) ^ set(map(int, c)))
            total += ref.unit_counts[layer]
        worst = max(worst, diff / total)
    return worst


def repeat_gap(a: Outcome, b: Outcome) -> float:
    fa, fb = final_params(a), final_params(b)
    return max(float(np.max(np.abs(fa[k] - fb[k]))) / max(float(np.max(np.abs(fb[k]))), 1e-30)
               for k in fb)


def numbers(cand: Outcome, ref: Outcome) -> Dict[str, float]:
    rounds = compared_rounds(cand, ref)
    K = rounds[-1]
    init = ref.globals[0]
    out = {}
    for t in rounds:
        g = list(leaf_gaps(cand, ref, t).values())
        out[f"change_gap_r{t}"] = max(g)
        out[f"median_change_gap_r{t}"] = float(np.median(g))
        out[f"layer_change_gap_r{t}"] = max(layer_gaps(cand, ref, t).values())
    out.update({
        "kept_units_mismatch": kept_units_mismatch(cand, ref),
        "retention_gap": float(max(abs(a - b) for a, b in zip(cand.retentions, ref.retentions))),
        "acc_gap": float(max(abs(a - b) for a, b in zip(cand.accs[1:K + 1], ref.accs[1:K + 1]))),
        "init_gap": max(float(np.max(np.abs(cand.globals[0][k] - init[k])))
                        / max(float(np.max(np.abs(init[k]))), 1e-30) for k in init),
    })
    return out
