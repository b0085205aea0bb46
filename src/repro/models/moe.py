"""Mixture-of-Experts FFN: sort-based (dropping) dispatch, expert-parallel.

Two execution paths:

* **Local path** (no mesh): plain sort-based dispatch — tokens routed top-k,
  sorted by expert, packed into a static ``[E, C, D]`` buffer, batched expert
  einsum, combined with router weights.  FLOPs are O(T * k * cf * D * F).

* **Expert-parallel path** (under a mesh): ``shard_map`` over (data, model).
  Activations are sharded over the data axis and replicated over the model
  axis; experts are sharded over the model axis.  Each device runs the local
  sort-based dispatch for its (token-shard x expert-shard) block and a single
  ``psum`` over the model axis combines expert contributions.  The global
  sort/scatter that defeats GSPMD (142 GiB/device of replicated dispatch
  buffers when left to auto-sharding — see EXPERIMENTS.md §Perf) never
  appears: every sort is device-local.

Experts are a prunable AdaptCL unit (whole-expert pruning); the router
renormalizes over retained experts automatically because pruned experts do
not exist in the reconfigured weights.

**Held share** (``held_moe_fwd``, DeepSeek-V3 routing, arXiv:2412.19437
§2.1.2): the layer of one expert-parallel rank.  It routes over all
``num_experts`` by sigmoid scores, chooses the top ``k`` by score plus a
correction bias (a constant of the model, never trained), weights the chosen
experts by their scores renormalised over the top ``k`` and scaled, and
computes the part of the output that its own ``held`` experts give, dropless:
every token routed to a held expert is computed, however uneven the routing,
by a sort and grouped matmuls (``lax.ragged_dot``).  What absent experts add
is left out.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.sharding.specs import current_mesh

from .layers import dense_init, silu

__all__ = ["MoESpec", "init_moe", "moe_fwd", "capacity", "HeldMoESpec",
           "sigmoid_route", "held_moe_fwd"]


@dataclasses.dataclass(frozen=True)
class MoESpec:
    d_model: int
    num_experts: int
    num_experts_per_tok: int
    d_ff: int                      # per-expert hidden size
    capacity_factor: float = 1.25
    shared_expert: bool = False    # Llama-4 style always-on expert
    shared_d_ff: Optional[int] = None
    router_aux_weight: float = 0.01


def capacity(spec: MoESpec, num_tokens: int) -> int:
    c = int(
        math.ceil(num_tokens * spec.num_experts_per_tok * spec.capacity_factor / spec.num_experts)
    )
    return max(8, ((c + 7) // 8) * 8)


def init_moe(key, spec: MoESpec, dtype=jnp.float32):
    kr, kg, ku, kd, ksg, ksu, ksd = jax.random.split(key, 7)
    E, D, F = spec.num_experts, spec.d_model, spec.d_ff
    p = {
        "w_router": dense_init(kr, D, E, dtype=jnp.float32),  # router in f32
        "w_gate": (dense_init(kg, D, (E, F), dtype=dtype)).transpose(1, 0, 2),  # [E,D,F]
        "w_up": (dense_init(ku, D, (E, F), dtype=dtype)).transpose(1, 0, 2),
        "w_down": (dense_init(kd, F, (E, D), dtype=dtype)).transpose(1, 0, 2),  # [E,F,D]
    }
    if spec.shared_expert:
        SF = spec.shared_d_ff or F
        p["ws_gate"] = dense_init(ksg, D, SF, dtype=dtype)
        p["ws_up"] = dense_init(ksu, D, SF, dtype=dtype)
        p["ws_down"] = dense_init(ksd, SF, D, dtype=dtype)
    return p


def _dispatch_compute_combine(params, spec: MoESpec, xf, probs, e_lo, n_local: int):
    """Sort-based dispatch restricted to experts [e_lo, e_lo + n_local).

    ``n_local`` is static (shapes depend on it); ``e_lo`` may be traced
    (it is ``axis_index * E_loc`` on the expert-parallel path).
    xf: [T, D] local tokens.  probs: [T, E_total] router probabilities.
    Returns (out [T, D], counts [E_total] local routing counts).
    """
    T, D = xf.shape
    k = spec.num_experts_per_tok
    E_here = n_local
    C = capacity(spec, T)

    gate, choice = jax.lax.top_k(probs, k)                     # [T,k] global ids
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    N = T * k
    flat_e = choice.reshape(N)
    counts_all = jnp.zeros((probs.shape[1],), jnp.int32).at[flat_e].add(1)

    local = (flat_e >= e_lo) & (flat_e < e_lo + E_here)
    loc_e = jnp.where(local, flat_e - e_lo, E_here)            # E_here = overflow bucket
    sort_idx = jnp.argsort(loc_e, stable=True)
    sorted_e = loc_e[sort_idx]
    counts = jnp.zeros((E_here + 1,), jnp.int32).at[loc_e].add(1)
    offsets = jnp.cumsum(counts) - counts
    pos_in_exp = jnp.arange(N, dtype=jnp.int32) - offsets[sorted_e]
    keep = (pos_in_exp < C) & (sorted_e < E_here)
    token_of = sort_idx // k
    dest = jnp.where(keep, sorted_e * C + jnp.clip(pos_in_exp, 0, C - 1), E_here * C)
    buf = (
        jnp.zeros((E_here * C + 1, D), xf.dtype)
        .at[dest]
        .add(xf[token_of] * keep[:, None].astype(xf.dtype))
    )[:-1].reshape(E_here, C, D)

    g = jnp.einsum("ecd,edf->ecf", buf, params["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", buf, params["w_up"])
    h = silu(g) * u
    out_buf = jnp.einsum("ecf,efd->ecd", h, params["w_down"]).reshape(E_here * C, D)
    out_buf = jnp.concatenate([out_buf, jnp.zeros((1, D), out_buf.dtype)], axis=0)

    gathered = out_buf[dest] * keep[:, None].astype(xf.dtype)  # [N,D]
    w = gate.reshape(N)[sort_idx].astype(xf.dtype)
    out = jnp.zeros((T, D), xf.dtype).at[token_of].add(gathered * w[:, None])
    return out, counts_all


def _moe_local(params, spec: MoESpec, x):
    b, s, D = x.shape
    T = b * s
    xf = x.reshape(T, D)
    E = params["w_gate"].shape[0]
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), params["w_router"][:, :E])
    probs = jax.nn.softmax(logits, axis=-1)
    out, counts = _dispatch_compute_combine(params, spec, xf, probs, 0, E)
    if spec.shared_expert:
        sh = silu(jnp.einsum("td,df->tf", xf, params["ws_gate"])) * jnp.einsum(
            "td,df->tf", xf, params["ws_up"]
        )
        out = out + jnp.einsum("tf,fd->td", sh, params["ws_down"])
    frac = counts.astype(jnp.float32) / jnp.maximum(T * spec.num_experts_per_tok, 1)
    aux = spec.router_aux_weight * E * jnp.sum(frac * probs.mean(axis=0))
    return out.reshape(b, s, D), aux


def moe_fwd(params, spec: MoESpec, x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (output [b,s,d], router load-balance aux loss scalar)."""
    mesh = current_mesh()
    E = params["w_gate"].shape[0]
    n_model = 0 if mesh is None else mesh.shape.get("model", 0)
    if not n_model or E % n_model != 0 or n_model == 1:
        return _moe_local(params, spec, x)

    ba = ("pod", "data") if "pod" in mesh.shape else ("data",)
    n_ba = 1
    for a in ba:
        n_ba *= mesh.shape[a]
    if x.shape[0] % n_ba != 0:
        ba = ()  # decode at batch 1 (long_500k): replicate tokens over data
    E_loc = E // n_model

    def inner(wr, wg, wu, wd, shared, xx):
        b, s, D = xx.shape
        T = b * s
        xf = xx.reshape(T, D)
        m = jax.lax.axis_index("model")
        logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), wr[:, :E])
        probs = jax.nn.softmax(logits, axis=-1)
        lo = m * E_loc
        lp = {"w_gate": wg, "w_up": wu, "w_down": wd}
        out, counts = _dispatch_compute_combine(lp, spec, xf, probs, lo, E_loc)
        if shared is not None:
            sg, su, sd = shared
            sh = silu(jnp.einsum("td,df->tf", xf, sg)) * jnp.einsum("td,df->tf", xf, su)
            out = out + jnp.einsum("tf,fd->td", sh, sd)
        out = jax.lax.psum(out, "model")
        frac = counts.astype(jnp.float32) / jnp.maximum(T * spec.num_experts_per_tok, 1)
        aux = spec.router_aux_weight * E * jnp.sum(frac * probs.mean(axis=0))
        for ax in (*ba, "model"):
            aux = jax.lax.pmean(aux, ax)
        return out.reshape(b, s, D), aux

    shared = None
    shared_specs = None
    if spec.shared_expert:
        shared = (params["ws_gate"], params["ws_up"], params["ws_down"])
        shared_specs = (P(None, "model"), P(None, "model"), P("model", None))
    out, aux = jax.shard_map(
        inner,
        mesh=mesh,
        in_specs=(P(), P("model"), P("model"), P("model"), shared_specs,
                  P(ba, None, None) if ba else P(None, None, None)),
        out_specs=(P(ba, None, None) if ba else P(None, None, None), P()),
        check_vma=False,
    )(params["w_router"], params["w_gate"], params["w_up"], params["w_down"], shared, x)
    return out, aux


# ---------------------------------------------------------------------------
# held share of a sigmoid-routed layer (DeepSeek-V3 routing, dropless)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class HeldMoESpec:
    num_experts: int               # the router's width: every expert of the layer
    num_experts_per_tok: int
    first_held: int                # held experts are first_held .. first_held + num_held - 1
    num_held: int
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True


def sigmoid_route(x, w_router, bias, spec: HeldMoESpec):
    """``(experts [T, k], weights [T, k])`` of tokens ``x [T, d]``: scores
    ``s = sigmoid(x Wr)`` in float32 (the product at full float32 precision
    on every backend), the top ``k`` of ``s + bias``, weighted
    ``scale * s_i / (sum_topk s_j + 1e-20)`` (or ``scale * s_i`` without
    renormalisation).  ``bias`` moves the choice, never the weights."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), w_router.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(s + bias, spec.num_experts_per_tok)
    g = jnp.take_along_axis(s, idx, axis=-1)
    if spec.norm_topk_prob:
        g = g / (g.sum(-1, keepdims=True) + 1e-20)
    return idx, g * spec.routed_scaling_factor


def held_moe_fwd(p, spec: HeldMoESpec, x, bias):
    """The held experts' part of the layer for tokens ``x [T, d]``:
    ``sum over i in top-k and held of g_i * E_i(x)``, with ``E_i`` a SwiGLU
    (``p["w_gate"], p["w_up"] [n_held, d, f]``, ``p["w_down"] [n_held, f,
    d]``).  Returns ``(out [T, d], load [n_held])``, the tokens each held
    expert computed."""
    T, d = x.shape
    k, n = spec.num_experts_per_tok, spec.num_held
    with jax.named_scope("moe.route"):
        idx, g = sigmoid_route(x, p["router"], bias, spec)
        local = idx.reshape(T * k) - spec.first_held
        held = (local >= 0) & (local < n)
        group = jnp.where(held, local, n)              # n: not held here
        order = jnp.argsort(group, stable=True)        # held rows first, by expert
        load = jnp.zeros((n + 1,), jnp.int32).at[group].add(1)[:n]
        tok = order // k
        w = jnp.where(held, g.reshape(T * k), 0.0)[order].astype(x.dtype)
    with jax.named_scope("moe.experts"):
        # ragged_dot leaves the rows past sum(load) (tokens routed
        # elsewhere) undefined, in its output and in its transpose for the
        # left operand: the TPU kernel skips their tiles.  Selecting zero in
        # those rows of the gathered tokens and of each grouped matmul's
        # output keeps them out of the result and, through the selects'
        # transposes, out of every gradient.
        valid = (jnp.arange(T * k) < load.sum())[:, None]

        def grouped(a, w_):
            return jnp.where(valid, jax.lax.ragged_dot(a, w_, load), 0)

        xs = jnp.where(valid, x[tok], 0)
        h = silu(grouped(xs, p["w_gate"])) * grouped(xs, p["w_up"])
        y = grouped(h, p["w_down"])
        out = jnp.zeros((T, d), x.dtype).at[tok].add(y * w[:, None])
    return out, load
