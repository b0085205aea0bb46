"""FLOPs and bytes of the fleet's models at given widths: the benchmark's
own count of the work a simulation requires.

A model family (``bench/models/<kind>.py``) lists the matmuls of one
sample's forward pass at the widths the worker retains (``matmuls``).
Forward FLOPs are ``2*M*K*N`` per matmul; forward and backward together are
three times the forward.  The least bytes of a training step are the f32
operands and result of each matmul, once for the forward, once for the
input gradient and once for the weight gradient.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

from bench.models import family


def matmuls(model: dict, kept: Optional[Dict[str, int]] = None) -> List[Tuple[int, int, int]]:
    """``(M per sample, K, N)`` of every matmul of the model's family at the
    retained widths ``kept`` (unit layer -> retained units; full width if
    absent)."""
    return family(model["kind"]).matmuls(model, kept)


def forward_flops(model: dict, kept: Optional[Dict[str, int]] = None) -> float:
    """Forward FLOPs per sample at the retained widths."""
    return sum(2.0 * m * k * n for m, k, n in matmuls(model, kept))


def step_bytes(model: dict, kept: Optional[Dict[str, int]], batch: int) -> float:
    """Least f32 bytes of one training step's matmuls (forward, dX, dW)."""
    return sum(3 * 4.0 * (batch * m * k + k * n + batch * m * n)
               for m, k, n in matmuls(model, kept))


def plan_steps(n: int, batch: int, epochs: float) -> int:
    """Steps of one local phase: ``round(epochs * n)`` samples in batches,
    the last one filled up."""
    if epochs <= 0 or n <= 0:
        return 0
    return -(-max(1, int(round(epochs * n))) // batch)


def sim_work(cfg: dict, settings: dict, prune_events: Sequence, shard_sizes: Sequence[int]) -> dict:
    """Samples, required training FLOPs and least matmul bytes of one
    simulation: every worker's scheduled steps in every round, at the widths
    it held in each phase (``prune_events``: ``(round, worker, {layer:
    retained ids})``; a worker prunes at ``beta`` of its local epoch)."""
    model, B, E = cfg["model"], cfg["batch_size"], cfg["local_epochs"]
    beta = settings.get("beta", 1.0)
    events = {(int(t), int(w)): {l: len(ids) for l, ids in idx.items()}
              for t, w, idx in prune_events}
    kept: List[Dict[str, int]] = [{} for _ in shard_sizes]
    images = flops = nbytes = 0.0
    cache: Dict[tuple, Tuple[float, float]] = {}

    def cost(k):
        key = tuple(sorted(k.items()))
        if key not in cache:
            cache[key] = (3.0 * forward_flops(model, k), step_bytes(model, k, B))
        return cache[key]

    for t in range(1, cfg["rounds"] + 1):
        for w, n in enumerate(shard_sizes):
            if (t, w) in events:
                phases = [(plan_steps(n, B, beta * E), kept[w])]
                kept[w] = events[(t, w)]
                phases.append((plan_steps(n, B, (1 - beta) * E), kept[w]))
            else:
                phases = [(plan_steps(n, B, E), kept[w])]
            for steps, k in phases:
                f, b = cost(k)
                images += steps * B
                flops += steps * B * f
                nbytes += steps * b
    return {"images": int(images), "flops": flops, "bytes": nbytes}
