"""Multi-head latent attention (MLA, DeepSeek-V2/V3, arXiv:2412.19437 §2.1.1)
without query compression (``q_lora_rank`` null).

For ``x`` of shape ``[b, s, d]`` and ``h`` heads:

* ``q = x Wq`` (``Wq [d, h, nope + rope]``), split per head into ``q_nope``
  and ``q_pe``;
* ``[c, k_pe] = x Wkva`` (``Wkva [d, rank + rope]``): the latent ``c`` and
  one rotary key ``k_pe`` shared by every head; ``c = rms(c) * g``;
* ``[k_nope, v] = c Wkvb`` (``Wkvb [rank, h, nope + v]``);
* rotary embedding on ``q_pe`` and ``k_pe``, rotating adjacent pairs
  (``rope_interleave``) with frequencies ``theta ** (-2i / rope)``;
* scores ``(q_nope . k_nope + q_pe . k_pe) / sqrt(nope + rope)``, a causal
  softmax, and the output ``concat_h(p v) Wo`` (``Wo [h, v, d]``).

A head owns its slices of ``Wq`` (axis 1), ``Wkvb`` (axis 1) and ``Wo``
(axis 0): the prunable unit of this layer.  ``Wkva`` and the latent norm are
shared by every head.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

__all__ = ["MLASpec", "rms_norm", "rope_interleaved", "mla_fwd"]


@dataclasses.dataclass(frozen=True)
class MLASpec:
    d_model: int
    heads: int
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    rope_theta: float = 1e6
    rms_eps: float = 1e-6


def rms_norm(x, g, eps: float):
    """``g * x / rms(x)`` in float32 (DeepSeek's RMSNorm: a plain scale)."""
    x = x.astype(jnp.float32)
    return g * (x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps))


def rope_interleaved(x, theta: float):
    """Rotary embedding of ``x [b, s, n, r]`` at positions ``0..s-1``, each
    adjacent pair ``(x[2i], x[2i+1])`` rotated by ``pos * theta**(-2i/r)``."""
    s, r = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).reshape(x.shape)


def mla_fwd(p, spec: MLASpec, x, precision=None):
    """``x [b, s, d]`` -> ``[b, s, d]``; ``p`` holds ``wq``, ``wkva``,
    ``kv_norm``, ``wkvb`` and ``wo``.  Heads come from the weights' shapes."""
    nope, rope, rank = spec.qk_nope_head_dim, spec.qk_rope_head_dim, spec.kv_lora_rank
    dot = lambda eq, a, b: jnp.einsum(eq, a, b, precision=precision)
    q = dot("bsd,dhe->bshe", x, p["wq"])
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    kva = dot("bsd,de->bse", x, p["wkva"])
    c = rms_norm(kva[..., :rank], p["kv_norm"], spec.rms_eps)
    k_pe = rope_interleaved(kva[:, :, None, rank:], spec.rope_theta)[:, :, 0]
    q_pe = rope_interleaved(q_pe, spec.rope_theta)
    kvb = dot("bsc,che->bshe", c, p["wkvb"])
    k_nope, v = kvb[..., :nope], kvb[..., nope:]
    scores = (dot("bshe,bthe->bhst", q_nope, k_nope)
              + dot("bshe,bte->bhst", q_pe, k_pe)) / jnp.sqrt(float(nope + rope))
    s = x.shape[1]
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = dot("bhst,bthe->bshe", probs, v)
    return dot("bshe,hed->bsd", o, p["wo"])
