"""What the CNN families share: the conv layer list, the reference's
convolution and BatchNorm, the initial model, the unit space of conv
filters, the image task, and the classification loss and hits.

A convolution is counted as the matmul it amounts to: ``M`` output pixels,
``K = k*k*cin`` and ``N = cout``, at the widths the worker retains (a pruned
input channel removes its ``k*k`` rows of ``K``).  A unit is an output
filter: its slice of the conv's weight, BN scale and shift, and the input
slice of the layer that consumes it (the next conv's weight, or the fc's).
BatchNorm normalises over the batch and space (batch statistics).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench.data import make_images

HIGHEST = lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Conv:
    name: str
    k: int
    stride: int
    cin: int
    cout: int
    hw: int          # output height = width


def make_task(model: dict, train_size: int, test_size: int, seed: int):
    """Seeded images of the model's size and classes (``bench/data.py``)."""
    return make_images(model["num_classes"], model["image_size"], train_size, test_size, seed)


def init_params(convs: Sequence[Conv], head_in: int, num_classes: int,
                seed: int) -> Dict[str, jnp.ndarray]:
    """He-normal (truncated at 2 sigma) convs, unit BN, fc at 1/sqrt(fan_in);
    one split key per layer, in network order."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 256))
    p: Dict[str, jnp.ndarray] = {}
    for c in convs:
        shape = (c.k, c.k, c.cin, c.cout)
        p[f"{c.name}/w"] = (
            jax.random.truncated_normal(next(keys), -2, 2, shape, jnp.float32)
            * np.sqrt(2.0 / (c.k * c.k * c.cin))
        )
        p[f"{c.name}/bn_g"] = jnp.ones((c.cout,))
        p[f"{c.name}/bn_b"] = jnp.zeros((c.cout,))
    p["fc/w"] = (
        jax.random.truncated_normal(next(keys), -2, 2, (head_in, num_classes), jnp.float32)
        * np.sqrt(1.0 / head_in)
    )
    p["fc/b"] = jnp.zeros((num_classes,))
    return p


def conv(x, w, stride, precision=HIGHEST):
    """``SAME`` convolution, NHWC x HWIO, as one matmul over the k*k shifted
    (and strided) views of the zero-padded input."""
    k, _, cin, cout = w.shape
    b, hh, ww, _ = x.shape
    out = -(-hh // stride)
    pad = max((out - 1) * stride + k - hh, 0)
    lo = pad // 2
    xp = jnp.pad(x, ((0, 0), (lo, pad - lo), (lo, pad - lo), (0, 0)))
    taps = [xp[:, i:i + (out - 1) * stride + 1:stride, j:j + (out - 1) * stride + 1:stride, :]
            for i in range(k) for j in range(k)]
    cols = jnp.concatenate(taps, axis=-1).reshape(b * out * out, k * k * cin)
    y = jnp.dot(cols, w.reshape(k * k * cin, cout), precision=precision)
    return y.reshape(b, out, out, cout)


def cbr(p, name, h, stride=1, relu=True, precision=HIGHEST):
    """Conv ``name``, BatchNorm on batch statistics, then ReLU if ``relu``."""
    h = conv(h, p[f"{name}/w"], stride, precision)
    mu = h.mean(axis=(0, 1, 2))
    var = h.var(axis=(0, 1, 2))
    h = (h - mu) * lax.rsqrt(var + 1e-5) * p[f"{name}/bn_g"] + p[f"{name}/bn_b"]
    return jnp.maximum(h, 0) if relu else h


def head(p, x, precision=HIGHEST):
    """Global mean pool and the fc: logits ``[b, classes]``."""
    x = x.mean(axis=(1, 2))
    return jnp.dot(x, p["fc/w"], precision=precision) + p["fc/b"]


def units(convs: Sequence[Conv], pairs: Sequence[Tuple[str, str]]) -> List[tuple]:
    """The unit space of ``pairs`` ``[(conv whose filters prune, its
    consumer)]``: a filter owns its conv's output slice, its BN scale and
    shift, and its consumer's input slice."""
    cout = {c.name: c.cout for c in convs}
    return [(name, cout[name],
             [(f"{name}/w", 3), (f"{name}/bn_g", 0), (f"{name}/bn_b", 0),
              ("fc/w", 0) if consumer == "fc" else (f"{consumer}/w", 2)])
            for name, consumer in pairs]


def unit_scale(p, layer: str):
    """A filter's BN scale (``|gamma|`` is CIG-BNscalor's importance)."""
    return p[f"{layer}/bn_g"]


def loss(out, y):
    """Mean cross-entropy of the logits against the labels."""
    logp = jax.nn.log_softmax(out)
    return -jnp.take_along_axis(logp, y[:, None], axis=1).mean()


def hits(out, y) -> int:
    """Samples whose largest logit is their label."""
    return int((np.asarray(out).argmax(-1) == y).sum())
