"""FLOPs and bytes of the fleet's CNNs at given widths: the benchmark's own
count of the work a simulation requires.

A convolution is counted as the matmul it amounts to: ``M`` output pixels,
``K = k*k*cin`` and ``N = cout``, at the widths the worker retains (a pruned
input channel removes its ``k*k`` rows of ``K``).  Forward FLOPs are
``2*M*K*N`` per layer plus the fc head; forward and backward together are
three times the forward.  The least bytes of a training step are the f32
operands and result of each matmul, once for the forward, once for the
input gradient and once for the weight gradient.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple


@dataclasses.dataclass(frozen=True)
class Conv:
    name: str
    k: int
    stride: int
    cin: int
    cout: int
    hw: int          # output height = width


def convs_of(model: dict) -> List[Conv]:
    """Every conv of the base model in network order."""
    hw = model["image_size"]
    out: List[Conv] = []
    if model["kind"] == "vgg":
        cin, i = 3, 0
        for e in model["plan"]:
            if e == "M":
                hw //= 2
                continue
            out.append(Conv(f"conv{i}", 3, 1, cin, int(e), hw))
            cin, i = int(e), i + 1
        return out
    out.append(Conv("stem", 3, 1, 3, model["stem"], hw))
    cin = model["stem"]
    for si, (nblocks, width) in enumerate(model["stages"]):
        for bi in range(nblocks):
            stride = 2 if (bi == 0 and si > 0) else 1
            hw //= stride
            pre = f"s{si}b{bi}"
            out.append(Conv(f"{pre}/c1", 3, stride, cin, width, hw))
            out.append(Conv(f"{pre}/c2", 3, 1, width, width, hw))
            if cin != width:
                out.append(Conv(f"{pre}/sc", 1, stride, cin, width, hw))
            cin = width
    return out


def head_width(model: dict) -> int:
    return convs_of(model)[-1].cout if model["kind"] == "vgg" else model["stages"][-1][1]


def prunable(model: dict) -> List[Tuple[str, str]]:
    """[(unit layer = conv whose output filters prune, its consumer)]."""
    cs = [c.name for c in convs_of(model)]
    if model["kind"] == "vgg":
        return [(n, cs[i + 1] if i + 1 < len(cs) else "fc") for i, n in enumerate(cs)]
    return [(n, n[:-2] + "c2") for n in cs if n.endswith("/c1")]


def matmuls(model: dict, kept: Optional[Dict[str, int]] = None) -> List[Tuple[int, int, int]]:
    """``(M per image, K, N)`` of every conv and of the head, at the retained
    widths ``kept`` (unit layer -> retained units; full width if absent)."""
    kept = kept or {}
    width = {c.name: kept.get(c.name, c.cout) for c in convs_of(model)}
    out = []
    prev = 3
    for c in convs_of(model):
        cin = c.cin
        if model["kind"] == "vgg":
            cin, prev = prev, width[c.name]
        elif c.name.endswith("/c2"):
            cin = width[c.name[:-2] + "c1"]
        out.append((c.hw * c.hw, c.k * c.k * cin, width[c.name]))
    fc_in = prev if model["kind"] == "vgg" else head_width(model)
    out.append((1, fc_in, model["num_classes"]))
    return out


def forward_flops(model: dict, kept: Optional[Dict[str, int]] = None) -> float:
    """Forward FLOPs per image at the retained widths."""
    return sum(2.0 * m * k * n for m, k, n in matmuls(model, kept))


def step_bytes(model: dict, kept: Optional[Dict[str, int]], batch: int) -> float:
    """Least f32 bytes of one training step's matmuls (forward, dX, dW)."""
    return sum(3 * 4.0 * (batch * m * k + k * n + batch * m * n)
               for m, k, n in matmuls(model, kept))


def plan_steps(n: int, batch: int, epochs: float) -> int:
    """Steps of one local phase: ``round(epochs * n)`` images in batches,
    the last one filled up."""
    if epochs <= 0 or n <= 0:
        return 0
    return -(-max(1, int(round(epochs * n))) // batch)


def sim_work(cfg: dict, settings: dict, prune_events: Sequence, shard_sizes: Sequence[int]) -> dict:
    """Images, required training FLOPs and least matmul bytes of one
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
