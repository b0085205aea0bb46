"""DeepSeek-V3-type decoder (``model_type: deepseek_v3``, arXiv:2412.19437
§2.1.1-2.1.2) as one rank of a stated deployment: the plain reference.

For layer ``l``: ``h = x + MLA(rms(x))``, ``y = h + F(rms(h))``; ``F`` is a
dense SwiGLU in the first ``first_k_dense_replace`` layers, else the held
experts' part of a sigmoid-routed expert layer plus the shared experts; a
final RMSNorm and an untied head.  MLA without query compression:
``q = x Wq`` split into ``q_nope``/``q_pe``; ``[c, k_pe] = x Wkva``;
``c = rms(c)``; ``[k_nope, v] = c Wkvb``; rotary embedding on ``q_pe`` and
``k_pe`` rotating adjacent pairs; scores ``[q_nope, q_pe].[k_nope, k_pe] /
sqrt(nope + rope)``, causal softmax, ``concat_h(p v) Wo``.  Routing: ``s =
sigmoid(x Wr)`` in float32 over every expert, the top ``k`` of ``s + b``
(``b`` the correction bias, 0), weights ``scale * s_i / (sum_topk s_j +
1e-20)``.  The held experts are the first ``experts_held`` (the first
expert-parallel rank's); each is computed densely over all tokens, weighted
by zero where a token is not routed to it.

A sample is one sequence of ``seq_len`` token ids from the held vocabulary
slice, each id stored as its two base-128 digits ``[..., 2]`` (high, low),
which every dtype the reference runs in (float32, bfloat16) holds exactly.
The task: ``domains`` seeded Markov chains, each walking within its own
topic of the vocabulary; a sequence's label (for the Non-IID split) is its
domain.  ``forward`` returns, per position but the last, the log-probability
of the next token and whether the largest logit is that token.

The initial model: one split key per leaf in sorted name order, matrices
normal(0, ``init_std``), norm scales 1.  Units: the heads of each layer, the
dense FFN's neurons, the shared experts' neurons, and the held experts'
neuron slots (slot ``u`` is neuron ``u`` of every held expert).
"""
from __future__ import annotations

import types
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
BASE = 128            # token ids as two base-128 digits
BRANCH = 4            # successors of a token in a domain's chain
BRANCH_P = (0.55, 0.25, 0.12, 0.08)
TOPIC = 1024          # tokens a domain's chain walks on

__all__ = ["program_model", "make_task", "init_params", "forward", "loss", "hits", "units",
           "unit_scale", "matmuls", "tiny", "leaf_shapes"]

PROGRAM_KEYS = ("hidden_size", "num_hidden_layers", "first_k_dense_replace",
                "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                "kv_lora_rank", "intermediate_size", "moe_intermediate_size",
                "n_routed_experts", "num_experts_per_tok", "experts_held",
                "n_shared_experts", "routed_scaling_factor", "norm_topk_prob", "vocab_size",
                "seq_len", "rms_norm_eps", "rope_theta", "init_std")


def program_model(name: str, model: dict) -> dict:
    from repro.models.deepseek_v3 import DeepSeekV3Config

    return {"model": DeepSeekV3Config(name=name, **{k: model[k] for k in PROGRAM_KEYS})}


def make_task(model: dict, train_size: int, test_size: int, seed: int):
    """Sequences ``[n, seq_len, 2]`` int32 (ids as base-128 digits) with
    their domain labels."""
    V, S, n_dom = model["vocab_size"], model["seq_len"], model["domains"]
    rng = np.random.default_rng(seed)
    topic = np.stack([rng.choice(V, min(TOPIC, V), replace=False) for _ in range(n_dom)])
    succ = topic[np.arange(n_dom)[:, None, None],
                 rng.integers(0, topic.shape[1], (n_dom, V, BRANCH))]

    def make(n: int, sub: int):
        r = np.random.default_rng([seed, sub])
        dom = r.integers(0, n_dom, n).astype(np.int32)
        cur = topic[dom, r.integers(0, topic.shape[1], n)]
        ids = np.empty((n, S), np.int64)
        for t in range(S):
            ids[:, t] = cur
            cur = succ[dom, cur, r.choice(BRANCH, n, p=BRANCH_P)]
        return np.stack([ids // BASE, ids % BASE], axis=-1).astype(np.int32), dom

    x_train, y_train = make(train_size, 1)
    x_test, y_test = make(test_size, 2)
    return types.SimpleNamespace(x_train=x_train, y_train=y_train, x_test=x_test, y_test=y_test)


def leaf_shapes(model: dict) -> Dict[str, tuple]:
    D, H, V = model["hidden_size"], model["num_attention_heads"], model["vocab_size"]
    nope, rope, vd, rank = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                            model["v_head_dim"], model["kv_lora_rank"])
    fe = model["moe_intermediate_size"]
    fs = model["n_shared_experts"] * fe
    out = {"embed/w": (V, D), "final/norm": (D,), "head/w": (D, V)}
    for l in range(model["num_hidden_layers"]):
        p = f"L{l}/"
        out.update({p + "attn/norm": (D,), p + "attn/wq": (D, H, nope + rope),
                    p + "attn/wkva": (D, rank + rope), p + "attn/kv_norm": (rank,),
                    p + "attn/wkvb": (rank, H, nope + vd), p + "attn/wo": (H, vd, D)})
        if l < model["first_k_dense_replace"]:
            F = model["intermediate_size"]
            out.update({p + "ffn/norm": (D,), p + "ffn/w_gate": (D, F), p + "ffn/w_up": (D, F),
                        p + "ffn/w_down": (F, D)})
        else:
            n = model["experts_held"]
            out.update({p + "moe/norm": (D,), p + "moe/router": (D, model["n_routed_experts"]),
                        p + "experts/w_gate": (n, D, fe), p + "experts/w_up": (n, D, fe),
                        p + "experts/w_down": (n, fe, D), p + "shared/w_gate": (D, fs),
                        p + "shared/w_up": (D, fs), p + "shared/w_down": (fs, D)})
    return out


def init_params(model: dict, seed: int) -> Dict[str, jnp.ndarray]:
    shapes = leaf_shapes(model)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    return {name: (jnp.ones(shapes[name], jnp.float32) if name.endswith("norm")
                   else jax.random.normal(k, shapes[name], jnp.float32) * model["init_std"])
            for k, name in zip(keys, sorted(shapes))}


def _rms(x, g, eps):
    return g * (x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps))


def _rope(x, theta):
    """Adjacent pairs of ``x [b, s, n, r]`` rotated by ``pos * theta**(-2i/r)``."""
    s, r = x.shape[1], x.shape[-1]
    inv = theta ** (-np.arange(0, r, 2) / r)
    ang = np.arange(s)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang)[:, None, :], x.dtype)
    sin = jnp.asarray(np.sin(ang)[:, None, :], x.dtype)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).reshape(x.shape)


def _swiglu(x, wg, wu, wd, precision):
    return jnp.dot(jax.nn.silu(jnp.dot(x, wg, precision=precision))
                   * jnp.dot(x, wu, precision=precision), wd, precision=precision)


def _mla(p, pre, model, x, precision):
    nope, rope, rank = model["qk_nope_head_dim"], model["qk_rope_head_dim"], model["kv_lora_rank"]
    ein = lambda eq, a, b: jnp.einsum(eq, a, b, precision=precision)
    q = ein("bsd,dhe->bshe", x, p[pre + "wq"])
    kva = ein("bsd,de->bse", x, p[pre + "wkva"])
    c = _rms(kva[..., :rank], p[pre + "kv_norm"], model["rms_norm_eps"])
    k_pe = _rope(kva[:, :, None, rank:], model["rope_theta"])
    kv = ein("bsc,che->bshe", c, p[pre + "wkvb"])
    h = q.shape[2]
    qf = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], model["rope_theta"])], -1)
    kf = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_pe, k_pe.shape[:2] + (h, rope))],
                         -1)
    scores = ein("bshe,bthe->bhst", qf, kf) / np.sqrt(nope + rope).astype(np.float32)
    s = x.shape[1]
    causal = np.tril(np.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = ein("bhst,bthe->bshe", probs, kv[..., nope:])
    return ein("bshe,hed->bsd", o, p[pre + "wo"])


def _experts(p, pre, model, x, precision):
    """The held experts' part: each computed over every token, weighted by
    the router's weight where the token chose it and by zero elsewhere."""
    k, E = model["num_experts_per_tok"], model["n_routed_experts"]
    s = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32), p[pre + "moe/router"].astype(jnp.float32),
                               precision=HIGHEST))
    _, idx = lax.top_k(s + jnp.zeros((E,), jnp.float32), k)
    g = jnp.take_along_axis(s, idx, axis=-1)
    if model["norm_topk_prob"]:
        g = g / (g.sum(-1, keepdims=True) + 1e-20)
    g = g * model["routed_scaling_factor"]
    out = jnp.zeros_like(x)
    for j in range(model["experts_held"]):
        weight = jnp.where(idx == j, g, 0.0).sum(-1).astype(x.dtype)
        e = [p[f"{pre}experts/{w}"][j] for w in ("w_gate", "w_up", "w_down")]
        out = out + weight[..., None] * _swiglu(x, *e, precision)
    return out


def forward(p, model: dict, x, precision=HIGHEST):
    """``[b, s - 1, 2]``: the log-probability of each next token, and 1
    where the largest logit is that token."""
    eps = model["rms_norm_eps"]
    ids = x[..., 0].astype(jnp.int32) * BASE + x[..., 1].astype(jnp.int32)
    h = p["embed/w"][ids]

    def layer(p, h, l):
        pre = f"L{l}/"
        h = h + _mla(p, pre + "attn/", model, _rms(h, p[pre + "attn/norm"], eps), precision)
        if l < model["first_k_dense_replace"]:
            xn = _rms(h, p[pre + "ffn/norm"], eps)
            return h + _swiglu(xn, p[pre + "ffn/w_gate"], p[pre + "ffn/w_up"],
                               p[pre + "ffn/w_down"], precision)
        xn = _rms(h, p[pre + "moe/norm"], eps)
        return (h + _experts(p, pre, model, xn, precision)
                + _swiglu(xn, p[pre + "shared/w_gate"], p[pre + "shared/w_up"],
                          p[pre + "shared/w_down"], precision))

    # each layer's activations are recomputed in the backward pass, so that
    # training at full size fits the device
    for l in range(model["num_hidden_layers"]):
        h = jax.checkpoint(layer, static_argnums=2)(p, h, l)
    logits = jnp.einsum("bsd,dv->bsv", _rms(h[:, :-1], p["final/norm"], eps), p["head/w"],
                        precision=precision)
    tgt = ids[:, 1:]
    lp = jnp.take_along_axis(jax.nn.log_softmax(logits), tgt[..., None], axis=-1)[..., 0]
    hit = (jnp.argmax(logits, axis=-1) == tgt).astype(lp.dtype)
    return jnp.stack([lp, hit], axis=-1)


def loss(out, y):
    """Mean negative log-probability of the next token."""
    return -out[..., 0].mean()


def hits(out, y) -> float:
    """Each sequence's token accuracy, summed over the batch."""
    return float(np.asarray(out, np.float64)[..., 1].mean(axis=-1).sum())


def _owns(layer: str) -> List[Tuple[str, int]]:
    """The ``(leaf, axis)`` slices a unit of ``layer`` owns."""
    kind = layer.rsplit("/", 1)[1]
    if kind == "attn":
        return [(layer + "/wq", 1), (layer + "/wkvb", 1), (layer + "/wo", 0)]
    lead = 1 if kind == "experts" else 0
    return [(layer + "/w_gate", lead + 1), (layer + "/w_up", lead + 1), (layer + "/w_down", lead)]


def units(model: dict) -> List[tuple]:
    shapes = leaf_shapes(model)
    out = []
    for l in range(model["num_hidden_layers"]):
        for kind in ("attn", "ffn", "shared", "experts"):
            layer = f"L{l}/{kind}"
            owns = _owns(layer)
            if owns[0][0] in shapes:
                out.append((layer, shapes[owns[0][0]][owns[0][1]], owns))
    return out


def unit_scale(p, layer: str):
    """This family has no per-unit scale (no BatchNorm), and the program
    refuses ``cig_bnscalor`` for it.  What the contract's CIG order would
    read here is each unit's weight norm over the slices it owns."""
    sq = 0.0
    for leaf, ax in _owns(layer):
        a = jnp.asarray(p[leaf], jnp.float32)
        sq = sq + jnp.sum(a * a, axis=tuple(d for d in range(a.ndim) if d != ax))
    return jnp.sqrt(sq)


def matmuls(model: dict, kept: Optional[Dict[str, int]] = None) -> List[Tuple[float, int, int]]:
    """``(M per sequence, K, N)`` at the retained counts ``kept``: scores over
    every pair of positions, the head over ``s - 1`` positions, each held
    expert at the balanced load ``s * k / n_routed_experts`` tokens."""
    kept = kept or {}
    S, D = model["seq_len"], model["hidden_size"]
    nope, rope, vd, rank = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                            model["v_head_dim"], model["kv_lora_rank"])
    E = model["n_routed_experts"]
    out: List[Tuple[float, int, int]] = []
    for l in range(model["num_hidden_layers"]):
        p = f"L{l}/"
        h = kept.get(p + "attn", model["num_attention_heads"])
        out += [(S, D, h * (nope + rope)), (S, D, rank + rope), (S, rank, h * (nope + vd)),
                (S * h, nope + rope, S), (S * h, S, vd), (S, h * vd, D)]
        if l < model["first_k_dense_replace"]:
            F = kept.get(p + "ffn", model["intermediate_size"])
            out += [(S, D, F), (S, D, F), (S, F, D)]
            continue
        fe = kept.get(p + "experts", model["moe_intermediate_size"])
        rows = S * model["num_experts_per_tok"] / E * model["experts_held"]
        fs = kept.get(p + "shared", model["n_shared_experts"] * model["moe_intermediate_size"])
        out += [(S, D, E), (rows, D, fe), (rows, D, fe), (rows, fe, D),
                (S, D, fs), (S, D, fs), (S, fs, D)]
    out.append((S - 1, D, model["vocab_size"]))
    return out


def tiny(model: dict) -> dict:
    return dict(model, hidden_size=64, num_hidden_layers=3, num_attention_heads=4,
                qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=32,
                intermediate_size=96, moe_intermediate_size=16, n_routed_experts=16,
                num_experts_per_tok=3, experts_held=4, vocab_size=256, seq_len=16, domains=4)
