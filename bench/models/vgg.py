"""VGG: conv-BN-ReLU layers and 2x2 max pools as ``model["plan"]`` lists
them (a width, or ``"M"`` for a pool), a global mean pool and an fc.

Every conv prunes by output filters, consumed by the next conv or the fc.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax.numpy as jnp
from jax import lax

from bench.models import _conv
from bench.models._conv import HIGHEST, Conv, hits, loss, make_task, unit_scale

__all__ = ["program_model", "make_task", "init_params", "forward", "loss", "hits", "units",
           "unit_scale", "matmuls", "tiny", "convs_of"]


def program_model(name: str, model: dict) -> dict:
    from repro.models.cnn import vgg_config

    return {"cnn": vgg_config(name, model["plan"], model["num_classes"], model["image_size"])}


def convs_of(model: dict) -> List[Conv]:
    """Every conv of the base model in network order."""
    hw = model["image_size"]
    out: List[Conv] = []
    cin, i = 3, 0
    for e in model["plan"]:
        if e == "M":
            hw //= 2
            continue
        out.append(Conv(f"conv{i}", 3, 1, cin, int(e), hw))
        cin, i = int(e), i + 1
    return out


def init_params(model: dict, seed: int) -> Dict[str, jnp.ndarray]:
    convs = convs_of(model)
    return _conv.init_params(convs, convs[-1].cout, model["num_classes"], seed)


def forward(p, model: dict, x, precision=HIGHEST):
    """Logits ``[b, classes]``."""
    i = 0
    for e in model["plan"]:
        if e == "M":
            x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
        else:
            x = _conv.cbr(p, f"conv{i}", x, precision=precision)
            i += 1
    return _conv.head(p, x, precision)


def units(model: dict) -> List[tuple]:
    cs = [c.name for c in convs_of(model)]
    return _conv.units(convs_of(model),
                       [(n, cs[i + 1] if i + 1 < len(cs) else "fc") for i, n in enumerate(cs)])


def matmuls(model: dict, kept: Optional[Dict[str, int]] = None) -> List[Tuple[int, int, int]]:
    """``(M per image, K, N)`` of every conv and of the head: a conv's input
    width is the previous conv's retained width."""
    kept = kept or {}
    out = []
    prev = 3
    for c in convs_of(model):
        width = kept.get(c.name, c.cout)
        out.append((c.hw * c.hw, c.k * c.k * prev, width))
        prev = width
    out.append((1, prev, model["num_classes"]))
    return out


def tiny(model: dict) -> dict:
    return dict(model, plan=[8, 8, "M", 16, "M", 16, "M"], image_size=8)
