"""Collaborative learning ON the mesh: workers as data-axis slices.

The FL simulator (`core.simulation`) reproduces the paper's host-level
protocol; this module maps the same semantics onto jax-native collectives for
the production mesh (DESIGN.md §2): every slice of the ``data`` axis is one
*worker* holding its private shard of the batch, sub-models are expressed as
nested CIG unit masks in base coordinates, and By-worker aggregation is a
single masked ``psum``:

    theta_g  =  (1/W) * psum_over_data( mask_w * theta_w )

Pruned coordinates contribute exact zeros — bitwise the paper's Alg. 1 line 5
semantics — and the aggregation collective appears in the lowered HLO like
any other production all-reduce (it is *the* communication the paper's
bandwidth model prices).

This file is deliberately model-agnostic: it works on flat {path: array}
params with a ``unit_map`` (same contract as core.aggregation), so the CNN
models and any future flat-parameter model can ride the same step.
"""
from __future__ import annotations

from typing import Callable, Dict, Mapping, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .aggregation import UnitMap
from .masks import GlobalIndex

__all__ = ["make_worker_masks", "collab_round", "local_sgd_steps"]

Params = Dict[str, jnp.ndarray]


def make_worker_masks(
    indices: Sequence[GlobalIndex],
    unit_map: UnitMap,
    base_shapes: Mapping[str, tuple],
) -> Params:
    """Stack per-worker coordinate masks: {path: [W, *shape] float32}."""
    from .aggregation import coordinate_mask

    out: Dict[str, np.ndarray] = {}
    for path, shape in base_shapes.items():
        ms = [coordinate_mask(path, idx, unit_map, base_shapes) for idx in indices]
        out[path] = np.stack(ms).astype(np.float32)
    return {k: jnp.asarray(v) for k, v in out.items()}


def local_sgd_steps(
    loss_fn: Callable[[Params, jnp.ndarray, jnp.ndarray], jnp.ndarray],
    params: Params,
    x: jnp.ndarray,
    y: jnp.ndarray,
    *,
    lr: float,
    steps: int,
    batch_size: int,
) -> Params:
    """`steps` plain-SGD minibatch steps on this worker's shard (jit-inlined)."""

    n = x.shape[0]

    def body(p, i):
        lo = (i * batch_size) % jnp.maximum(n - batch_size + 1, 1)
        xb = jax.lax.dynamic_slice_in_dim(x, lo, batch_size, axis=0)
        yb = jax.lax.dynamic_slice_in_dim(y, lo, batch_size, axis=0)
        g = jax.grad(loss_fn)(p, xb, yb)
        return jax.tree.map(lambda w, gw: w - lr * gw, p, g), None

    params, _ = jax.lax.scan(body, params, jnp.arange(steps))
    return params


def collab_round(
    loss_fn: Callable,
    global_params: Params,
    masks: Params,           # [W, *shape] per path (make_worker_masks)
    x: jnp.ndarray,          # [W * n_local, ...] worker-sharded data
    y: jnp.ndarray,
    mesh,
    *,
    lr: float = 0.05,
    steps: int = 4,
    batch_size: int = 32,
    axis: str = "data",
) -> Params:
    """One synchronous AdaptCL round as a single SPMD program.

    Each ``data`` slice: extract its sub-model (mask), run local SGD on its
    shard, submit; the server aggregation is the closing masked psum / W.
    Returns the new global (base-coordinate) parameters, replicated.
    """
    W = mesh.shape[axis]

    def worker(gp, mask_w, xw, yw):
        # theta_w = theta_g ⊙ I_w  (masked extraction; reconfigured-shape
        # extraction is the simulator's job — here shapes stay static so the
        # whole round is one XLA program)
        mask_w = jax.tree.map(lambda m: m[0], mask_w)          # [1,*] -> [*]
        theta = jax.tree.map(lambda g, m: g * m, gp, mask_w)

        def masked_loss(p, xb, yb):
            return loss_fn(jax.tree.map(lambda w, m: w * m, p, mask_w), xb, yb)

        theta = local_sgd_steps(masked_loss, theta, xw, yw, lr=lr,
                                steps=steps, batch_size=batch_size)
        theta = jax.tree.map(lambda w, m: w * m, theta, mask_w)
        # By-worker aggregation: pruned coords are zeros; coefficient 1/W
        return jax.tree.map(lambda w: jax.lax.psum(w, axis) / W, theta)

    pspec_rep = jax.tree.map(lambda _: P(), global_params)
    pspec_masks = jax.tree.map(lambda _: P(axis), masks)
    return jax.shard_map(
        worker,
        mesh=mesh,
        in_specs=(pspec_rep, pspec_masks, P(axis), P(axis)),
        out_specs=pspec_rep,
        check_vma=False,
    )(global_params, masks, x, y)
