"""Jit'd public wrappers around the Pallas kernels.

On the CPU backend the kernels execute in ``interpret=True`` mode — the
kernel body runs as Python/jnp over the same BlockSpec tiling, which is what
the allclose tests validate.  On a TPU backend they compile to Mosaic.
``auto_interpret()`` picks per backend and refuses any other one, so a run
never falls back to the interpreter by accident.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention_kernel_call
from .pruned_matmul import pruned_matmul as pruned_matmul_ad
from .rg_lru_scan import rg_lru_scan_kernel_call

__all__ = ["auto_interpret", "pruned_matmul", "flash_attention", "rg_lru_scan"]


def auto_interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(
            f"the Pallas kernels compile for TPU and interpret on CPU; "
            f"backend {backend!r} is neither — pass interpret= explicitly"
        )
    return backend == "cpu"


def pruned_matmul(x, w, in_mask, out_mask, row_mask=None, **kw):
    """AdaptCL masked-training matmul: y = (x * in_mask) @ w * out_mask with
    whole pruned M/K/N blocks skipped.  Masks are 0/1 vectors in base
    coordinates; differentiable (custom VJP reuses the block-skip kernel),
    and any shape is accepted (padded to block multiples internally)."""
    kw.setdefault("interpret", auto_interpret())
    return pruned_matmul_ad(x, w, in_mask, out_mask, row_mask, **kw)


def flash_attention(q, k, v, *, causal=True, window: Optional[int] = None,
                    softcap: Optional[float] = None, **kw):
    """Blocked online-softmax attention; K/V pre-repeated to query heads."""
    kw.setdefault("interpret", auto_interpret())
    return flash_attention_kernel_call(
        q, k, v, causal=causal, window=window, softcap=softcap, **kw
    )


def rg_lru_scan(x, a, h0=None, **kw):
    """RG-LRU linear recurrence h_t = a_t h_{t-1} + x_t over seq blocks."""
    if h0 is None:
        h0 = jnp.zeros((x.shape[0], x.shape[2]), jnp.float32)
    kw.setdefault("interpret", auto_interpret())
    return rg_lru_scan_kernel_call(x, a, h0, **kw)
