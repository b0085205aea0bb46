"""ResNet with basic blocks: a 3x3 conv-BN-ReLU stem, ``model["stages"]``
of ``[blocks, width]`` (two 3x3 convs a block, stride 2 at the first block
of every stage after the first, a 1x1 projection shortcut where the width
changes), a global mean pool and an fc.

The first conv of every block prunes by output filters, consumed by the
block's second conv; the stem, second convs and shortcuts keep their width
(the residual sum fixes it).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax.numpy as jnp

from bench.models import _conv
from bench.models._conv import HIGHEST, Conv, hits, loss, make_task, unit_scale

__all__ = ["program_model", "make_task", "init_params", "forward", "loss", "hits", "units",
           "unit_scale", "matmuls", "tiny", "convs_of"]


def program_model(name: str, model: dict) -> dict:
    from repro.models.cnn import resnet_config

    return {"cnn": resnet_config(name, model["stem"], [tuple(s) for s in model["stages"]],
                                 model["num_classes"], model["image_size"], bottleneck=False)}


def convs_of(model: dict) -> List[Conv]:
    """Every conv of the base model in network order."""
    hw = model["image_size"]
    out: List[Conv] = [Conv("stem", 3, 1, 3, model["stem"], hw)]
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


def init_params(model: dict, seed: int) -> Dict[str, jnp.ndarray]:
    return _conv.init_params(convs_of(model), model["stages"][-1][1], model["num_classes"], seed)


def forward(p, model: dict, x, precision=HIGHEST):
    """Logits ``[b, classes]``."""
    x = _conv.cbr(p, "stem", x, precision=precision)
    for si, (nblocks, _) in enumerate(model["stages"]):
        for bi in range(nblocks):
            pre = f"s{si}b{bi}"
            stride = 2 if (bi == 0 and si > 0) else 1
            h = _conv.cbr(p, f"{pre}/c2", _conv.cbr(p, f"{pre}/c1", x, stride, precision=precision),
                          relu=False, precision=precision)
            if f"{pre}/sc/w" in p:
                x = _conv.cbr(p, f"{pre}/sc", x, stride, relu=False, precision=precision)
            elif stride != 1:
                x = x[:, ::stride, ::stride, :]
            x = jnp.maximum(x + h, 0)
    return _conv.head(p, x, precision)


def units(model: dict) -> List[tuple]:
    convs = convs_of(model)
    return _conv.units(convs, [(c.name, c.name[:-2] + "c2") for c in convs
                               if c.name.endswith("/c1")])


def matmuls(model: dict, kept: Optional[Dict[str, int]] = None) -> List[Tuple[int, int, int]]:
    """``(M per image, K, N)`` of every conv and of the head: a block's
    second conv reads its first conv's retained width."""
    kept = kept or {}
    convs = convs_of(model)
    width = {c.name: kept.get(c.name, c.cout) for c in convs}
    out = []
    for c in convs:
        cin = width[c.name[:-2] + "c1"] if c.name.endswith("/c2") else c.cin
        out.append((c.hw * c.hw, c.k * c.k * cin, width[c.name]))
    out.append((1, model["stages"][-1][1], model["num_classes"]))
    return out


def tiny(model: dict) -> dict:
    return dict(model, stem=8, stages=[[2, 8], [1, 16]], image_size=8)
