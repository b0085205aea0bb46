"""Pruned (block-sparse) matmul Pallas kernel — AdaptCL's masked-training hot spot.

TPU adaptation of the paper's sub-model compute (DESIGN.md §2): instead of a
GPU gather-matmul, unit pruning is expressed as 0/1 masks over the K (input
units) and N (output units) dims — plus an optional row mask over M — and the
kernel is a 128-aligned blocked matmul that (a) applies the masks fused in
VMEM (no separate ``W * mask`` materialization in HBM) and (b) *skips whole
blocks* whose units are all pruned, via scalar-prefetched block-keep flags —
the MXU-granular analogue of NetworkReconfigure.  Skipping is three-way:

* ``k_keep`` — a K (contraction) block with no surviving input unit
  contributes nothing to the accumulator, so its MXU pass is skipped;
* ``n_keep`` — an N (output-column) block whose units are all pruned can only
  produce zeros, so its accumulation is skipped and the finish pass writes the
  zeros via the fused ``out_mask`` multiply;
* ``m_keep`` — same for fully-masked row blocks (``row_mask``), which is what
  lets the backward pass skip pruned *output-unit rows* of dW.

With CIG pruning the retained set is a fixed prefix of the frozen importance
order, so after the one-time relabeling of units into that order (the
``index`` importance method is exactly this relabeled view) the retained set
is a coordinate prefix: whole tail blocks die at once, block occupancy of the
surviving prefix stays high, and executed FLOPs scale ~ with the retention
ratio instead of rounding up per scattered unit.

Shapes need not be multiples of the block sizes: inputs are zero-padded up to
block multiples (padded mask entries are 0, so padded blocks are *skipped*,
not computed) and the output is sliced back to ``[M, N]``.

Grid: (M/bm, N/bn, K/bk), K innermost (sequential); fp32 VMEM accumulator.

``pruned_matmul`` is the differentiable entry point: a ``jax.custom_vjp``
whose backward pass reuses this same kernel —

    dX = ((dY * out_mask) @ Wᵀ) * in_mask * row_mask   (skips pruned N blocks
                                                        in the contraction and
                                                        pruned K output blocks)
    dW = ((Xᵀ * row_mask) @ dY) * in_mask[:,None] * out_mask[None,:]
                                                       (skips pruned K row
                                                        blocks and N column
                                                        blocks)

so masked gradients are exactly zero on pruned units (the fleet invariant:
``core.fleet.FleetState`` param rows stay exactly 0 on pruned coordinates)
and backward FLOPs track retention the same way forward FLOPs do.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "pruned_matmul_kernel_call",
    "pruned_matmul",
    "block_keep_count",
    "matmul_executed_blocks",
    "matmul_executed_flops",
]


def _kernel(
    m_keep_ref, k_keep_ref, n_keep_ref,
    x_ref, w_ref, in_mask_ref, out_mask_ref, row_mask_ref,
    o_ref, acc_ref,
):
    mi = pl.program_id(0)
    ni = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(
        (m_keep_ref[mi] > 0) & (n_keep_ref[ni] > 0) & (k_keep_ref[ki] > 0)
    )
    def _compute():
        xm = x_ref[...].astype(jnp.float32) * in_mask_ref[...].astype(jnp.float32)
        acc_ref[...] += jax.lax.dot_general(
            xm,
            w_ref[...].astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[...] = (
            acc_ref[...]
            * out_mask_ref[...].astype(jnp.float32)
            * row_mask_ref[...].astype(jnp.float32)
        ).astype(o_ref.dtype)


def _pad_to(a: jnp.ndarray, mults) -> jnp.ndarray:
    pads = [(0, -int(s) % int(m)) for s, m in zip(a.shape, mults)]
    if any(p for _, p in pads):
        a = jnp.pad(a, pads)
    return a


def _check_blocks(block_m: int, block_n: int, block_k: int, *, grad: bool) -> None:
    """Reject tiles the compiled (Mosaic) kernel cannot lay out.  K and N
    blocks are lane dims: multiples of 128.  The custom VJP re-orients the
    kernel so that dW contracts over M, which makes block_m a lane dim too
    once gradients flow.  Interpret mode takes any size."""
    lanes = (block_m, block_n, block_k) if grad else (block_n, block_k)
    if any(b % 128 for b in lanes) or block_m % 8:
        need = "all three" if grad else "block_n and block_k (block_m: of 8)"
        raise ValueError(
            f"compute_blocks=({block_m}, {block_n}, {block_k}): the compiled "
            f"TPU kernel needs multiples of 128 for {need}; smaller tiles run "
            "in interpret mode only"
        )


def _keep_flags(mask: jnp.ndarray, block: int) -> jnp.ndarray:
    """1 per block if any unit in the block survives (scalar prefetch).
    ``mask`` must already be padded to a multiple of ``block``."""
    nb = mask.shape[0] // block
    return (mask.reshape(nb, block).sum(axis=1) > 0).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k", "interpret"))
def _call(
    x: jnp.ndarray,          # [M, K]
    w: jnp.ndarray,          # [K, N]
    in_mask: jnp.ndarray,    # [K] 0/1
    out_mask: jnp.ndarray,   # [N] 0/1
    row_mask: jnp.ndarray,   # [M] 0/1
    block_m: int,
    block_n: int,
    block_k: int,
    interpret: bool,
) -> jnp.ndarray:
    M, K = x.shape
    K2, N = w.shape
    assert K == K2 and in_mask.shape == (K,) and out_mask.shape == (N,)
    assert row_mask.shape == (M,)
    # ragged shapes: zero-pad up to block multiples; padded mask entries are
    # 0, so padded blocks are skipped entirely, and the output is sliced back
    x = _pad_to(x, (block_m, block_k))
    w = _pad_to(w, (block_k, block_n))
    in_mask = _pad_to(in_mask, (block_k,))
    out_mask = _pad_to(out_mask, (block_n,))
    row_mask = _pad_to(row_mask, (block_m,))
    Mp, Kp = x.shape
    Np = w.shape[1]

    m_keep = _keep_flags(row_mask, block_m)
    k_keep = _keep_flags(in_mask, block_k)
    n_keep = _keep_flags(out_mask, block_n)
    # masks enter the kernel 2-D — [1, K] / [1, N] rows and an [M, 1]
    # column — so every block is a (sublane, lane) tile Mosaic can lay out
    # and the body broadcasts them without a reshape

    grid = (Mp // block_m, Np // block_n, Kp // block_k)
    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_m, block_k), lambda i, j, k, *_: (i, k)),
                pl.BlockSpec((block_k, block_n), lambda i, j, k, *_: (k, j)),
                pl.BlockSpec((1, block_k), lambda i, j, k, *_: (0, k)),
                pl.BlockSpec((1, block_n), lambda i, j, k, *_: (0, j)),
                pl.BlockSpec((block_m, 1), lambda i, j, k, *_: (i, 0)),
            ],
            out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k, *_: (i, j)),
            scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), x.dtype),
        interpret=interpret,
        name="pruned_matmul",    # the kernel's name in traces and HLO
    )(m_keep, k_keep, n_keep, x, w,
      in_mask[None, :], out_mask[None, :], row_mask[:, None])
    return out[:M, :N]


def pruned_matmul_kernel_call(
    x: jnp.ndarray,          # [M, K]
    w: jnp.ndarray,          # [K, N]
    in_mask: jnp.ndarray,    # [K] 0/1
    out_mask: jnp.ndarray,   # [N] 0/1
    row_mask: jnp.ndarray | None = None,   # [M] 0/1 (default: all rows live)
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Forward-only kernel call (no autodiff rule); see ``pruned_matmul``."""
    if not interpret:
        _check_blocks(block_m, block_n, block_k, grad=False)
    if row_mask is None:
        row_mask = jnp.ones((x.shape[0],), jnp.float32)
    return _call(x, w, in_mask, out_mask, row_mask, block_m, block_n, block_k, interpret)


# ---------------------------------------------------------------------------
# custom VJP: the backward pass is the same block-skip kernel, re-oriented
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _pm_ad(x, w, in_mask, out_mask, row_mask, block_m, block_n, block_k, interpret):
    return _call(x, w, in_mask, out_mask, row_mask, block_m, block_n, block_k, interpret)


def _pm_fwd(x, w, in_mask, out_mask, row_mask, block_m, block_n, block_k, interpret):
    y = _call(x, w, in_mask, out_mask, row_mask, block_m, block_n, block_k, interpret)
    return y, (x, w, in_mask, out_mask, row_mask)


def _pm_bwd(block_m, block_n, block_k, interpret, res, g):
    x, w, in_mask, out_mask, row_mask = res
    g = g.astype(x.dtype)
    # dX [M, K] = ((g * out_mask) @ Wᵀ) * in_mask[None, :] * row_mask[:, None]
    # contraction over N skips pruned N blocks; pruned K output blocks skip too
    dx = _call(
        g, w.T, out_mask, in_mask, row_mask,
        block_m, block_k, block_n, interpret,
    )
    # dW [K, N] = ((Xᵀ * row_mask) @ g) * in_mask[:, None] * out_mask[None, :]
    # pruned K row blocks and pruned N column blocks are both skipped
    dw = _call(
        x.T, g, row_mask, out_mask, in_mask,
        block_k, block_n, block_m, interpret,
    )
    return (
        dx, dw,
        jnp.zeros_like(in_mask), jnp.zeros_like(out_mask), jnp.zeros_like(row_mask),
    )


_pm_ad.defvjp(_pm_fwd, _pm_bwd)


def pruned_matmul(
    x: jnp.ndarray,
    w: jnp.ndarray,
    in_mask: jnp.ndarray,
    out_mask: jnp.ndarray,
    row_mask: jnp.ndarray | None = None,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Differentiable block-skip masked matmul:
    ``y = ((x * in_mask) @ w) * out_mask[None, :] * row_mask[:, None]``.

    Gradients flow to ``x`` and ``w`` only (masks are treated as constant 0/1
    structure) and are *exactly* zero on pruned units.  Any M/K/N is accepted
    (padded to block multiples internally); vmap-able over a leading batch
    axis with per-row masks — the resident fleet's one-program dispatch.
    """
    if not interpret:
        _check_blocks(block_m, block_n, block_k, grad=True)
    if row_mask is None:
        row_mask = jnp.ones((x.shape[0],), jnp.float32)
    return _pm_ad(x, w, in_mask, out_mask, row_mask, block_m, block_n, block_k, interpret)


# ---------------------------------------------------------------------------
# host-side block accounting (the interpret-mode FLOPs proxy)
# ---------------------------------------------------------------------------

def block_keep_count(mask: np.ndarray, block: int) -> int:
    """Number of blocks with >= 1 surviving unit, after padding to a multiple
    of ``block`` (the same flags the kernel prefetches)."""
    mask = np.asarray(mask)
    pad = -len(mask) % block
    if pad:
        mask = np.concatenate([mask, np.zeros(pad, mask.dtype)])
    return int((mask.reshape(-1, block).sum(axis=1) > 0).sum())


def matmul_executed_blocks(
    M: int,
    in_mask: np.ndarray,
    out_mask: np.ndarray,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
) -> int:
    """Grid cells whose MXU pass actually executes (rows assumed all live)."""
    m_blocks = -(-M // block_m)
    return m_blocks * block_keep_count(in_mask, block_k) * block_keep_count(out_mask, block_n)


def matmul_executed_flops(
    M: int,
    in_mask: np.ndarray,
    out_mask: np.ndarray,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
) -> float:
    """Forward multiply-add FLOPs the kernel executes: 2 * M * K_exec * N_exec
    where K_exec/N_exec count *blocks kept*, not units kept — the honest
    device cost of block-granular skipping (M is not padded: the row dim is
    batch-dependent and never pruned in the forward pass)."""
    k_exec = block_keep_count(in_mask, block_k) * block_k
    n_exec = block_keep_count(out_mask, block_n) * block_n
    return 2.0 * M * k_exec * n_exec
