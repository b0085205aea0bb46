"""DeepSeek-V3-type decoder (``model_type: deepseek_v3``, arXiv:2412.19437
§2.1) as one rank of a stated deployment: the model interface of
``models.interface`` for the fleet simulator.

Pre-norm residual blocks, for layer ``l``: ``h = x + MLA(rms(x))``, then
``y = h + F(rms(h))``, where ``F`` is a dense SwiGLU in the first
``first_k_dense_replace`` layers and otherwise the held share of a
sigmoid-routed expert layer (``moe.held_moe_fwd``) plus the shared experts
(one SwiGLU of width ``n_shared_experts * moe_intermediate_size``).  A final
RMSNorm and an untied head follow.  A sample is one sequence of
``seq_len`` token ids, each as its two base-128 digits ``[s, 2]`` (high,
low: the benchmark's reference casts its inputs to its compute dtype, and
bfloat16 holds such digits exactly); its outputs are, per position, the
log-probability of the next token and whether the largest logit is that
token.

The rank holds ``num_attention_heads`` heads, the first ``experts_held``
routed experts (the first expert-parallel rank's) of the router's
``n_routed_experts``, and ``vocab_size`` rows of the vocabulary; what the
absent heads, experts and rows would add is left out.  Every width is the
published one.  The routing correction bias is 0, a constant of the model.

Prunable units: the heads of each layer (``L{l}/attn``), the dense FFN's
neurons (``L0/ffn``), the shared experts' neurons (``L{l}/shared``) and the
held experts' neuron slots (``L{l}/experts``: slot ``u`` is neuron ``u`` of
every held expert, so a layer's held experts are pruned to one width).  The
router, the latent projection, the norms, the embedding and the head are
fixed.  There is no per-unit scale (no BatchNorm), so CIG-BNscalor does not
apply; the seed-derived orders do.

Each layer is rematerialised in the backward pass (``jax.checkpoint``), and
the fleet holds the model in its lean layout (``fleet_layout = "lean"``):
one worker trains after another, since the grouped matmul has no batched
form on the TPU and one worker's activations at a time is what fits, and
masks broadcast from per-unit vectors.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.masks import UnitLayer, UnitSpace

from .ffn import FFNSpec, ffn_fwd
from .mla import MLASpec, mla_fwd, rms_norm
from .moe import HeldMoESpec, held_moe_fwd

__all__ = ["DeepSeekV3Config", "leaf_shapes", "matmuls"]


@dataclasses.dataclass(frozen=True)
class DeepSeekV3Config:
    name: str
    hidden_size: int = 2048
    num_hidden_layers: int = 5
    first_k_dense_replace: int = 1
    num_attention_heads: int = 4          # heads held
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    intermediate_size: int = 6144         # dense FFN
    moe_intermediate_size: int = 768      # one expert
    n_routed_experts: int = 128           # the router's width
    num_experts_per_tok: int = 6
    experts_held: int = 8
    n_shared_experts: int = 2
    routed_scaling_factor: float = 2.448
    norm_topk_prob: bool = True
    vocab_size: int = 16032               # rows held
    seq_len: int = 2048
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    init_std: float = 0.02

    compute_paths = ("dense",)
    importances = frozenset({"index", "no_adjacent", "no_identical", "no_constant"})
    fleet_layout = "lean"

    # ---- the model interface -------------------------------------------

    def init(self, key) -> Dict[str, jnp.ndarray]:
        """Matrices normal(0, init_std), norm scales 1; one split key per
        leaf in sorted name order."""
        shapes = leaf_shapes(self)
        keys = jax.random.split(key, len(shapes))
        out = {}
        for k, name in zip(keys, sorted(shapes)):
            if name.endswith("norm"):
                out[name] = jnp.ones(shapes[name], jnp.float32)
            else:
                out[name] = jax.random.normal(k, shapes[name], jnp.float32) * self.init_std
        return out

    def unit_space(self, params):
        layers, unit_map = [], {}
        for lname, count, owns in units(self):
            cost = sum(int(np.prod(params[p].shape)) // params[p].shape[ax] for p, ax in owns)
            layers.append(UnitLayer(name=lname, num_units=count, unit_param_cost=cost,
                                    min_units=2))
            for p, ax in owns:
                unit_map.setdefault(p, []).append((lname, ax))
        total = sum(int(np.prod(v.shape)) for v in params.values())
        fixed = total - sum(l.num_units * l.unit_param_cost for l in layers)
        return UnitSpace(layers=tuple(layers), fixed_params=fixed), unit_map

    def apply(self, params, x, **compute):
        """``x [b, s, 2]`` token ids (base-128 digits) -> ``{"lp", "hit"}`` of
        shape ``[b, s-1]``
        (log-probability of the next token, whether it is the top logit)
        and the step's expert counters ``held_tokens`` (tokens routed to held
        experts) and ``held_load_max`` (the largest held expert's tokens),
        each summed over the expert layers."""
        if compute.get("compute", "dense") != "dense":
            raise ValueError(f"{self.name}: dense compute only")
        ids = x[..., 0] * 128 + x[..., 1]
        h = params["embed/w"][ids]
        tokens = load_max = jnp.zeros((), jnp.int32)
        for l in range(self.num_hidden_layers):
            pre = f"L{l}/"
            lp = {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}
            h, load = jax.checkpoint(self._block(l))(lp, h)
            if load is not None:
                tokens = tokens + load.sum()
                load_max = load_max + load.max()
        with jax.named_scope("lm_head"):
            hn = rms_norm(h[:, :-1], params["final/norm"], self.rms_norm_eps)
            logits = jnp.einsum("bsd,dv->bsv", hn, params["head/w"])
            tgt = ids[:, 1:]
            logp = jax.nn.log_softmax(logits)
            lp_next = jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
            hit = jnp.argmax(logits, axis=-1) == tgt
        return {"lp": lp_next, "hit": hit, "held_tokens": tokens, "held_load_max": load_max}

    def loss(self, out, y):
        """Mean negative log-probability of the next token."""
        return -out["lp"].mean()

    def counters(self, out) -> dict:
        return {"held_tokens": out["held_tokens"], "held_load_max": out["held_load_max"]}

    def eval_outputs(self, params, x):
        """Each sequence's token accuracy ``[b]``, one sequence at a time."""
        return jax.lax.map(lambda seq: self.apply(params, seq[None])["hit"].mean(), x)

    def hits(self, acc, y) -> float:
        return float(np.sum(np.asarray(acc, np.float64)))

    def flops_from_shapes(self, shapes) -> float:
        return sum(2.0 * m * k * n for m, k, n in matmuls(self, shapes))

    def unit_scales(self, params):
        raise ValueError(f"{self.name}: no per-unit scale (no BatchNorm) for cig_bnscalor")

    def default_task(self, seed: int):
        raise ValueError(f"{self.name}: a token task must be given (SimConfig.task)")

    # ---- blocks ----------------------------------------------------------

    def _mla_spec(self) -> MLASpec:
        return MLASpec(self.hidden_size, self.num_attention_heads, self.qk_nope_head_dim,
                       self.qk_rope_head_dim, self.v_head_dim, self.kv_lora_rank,
                       self.rope_theta, self.rms_norm_eps)

    def moe_spec(self) -> HeldMoESpec:
        return HeldMoESpec(self.n_routed_experts, self.num_experts_per_tok, 0,
                           self.experts_held, self.routed_scaling_factor, self.norm_topk_prob)

    def _block(self, l: int):
        eps, dense = self.rms_norm_eps, l < self.first_k_dense_replace
        bias = jnp.zeros((self.n_routed_experts,), jnp.float32)
        glu = FFNSpec(self.hidden_size, 0)

        def block(p, h):
            with jax.named_scope("mla"):
                a = {k[5:]: v for k, v in p.items() if k.startswith("attn/")}
                h = h + mla_fwd(a, self._mla_spec(), rms_norm(h, a["norm"], eps))
            if dense:
                with jax.named_scope("ffn"):
                    f = {k[4:]: v for k, v in p.items() if k.startswith("ffn/")}
                    return h + ffn_fwd(f, glu, rms_norm(h, f["norm"], eps)), None
            b, s, d = h.shape
            x = rms_norm(h, p["moe/norm"], eps)
            e = {k[8:]: v for k, v in p.items() if k.startswith("experts/")}
            out, load = held_moe_fwd(dict(e, router=p["moe/router"]), self.moe_spec(),
                                     x.reshape(b * s, d), bias)
            with jax.named_scope("moe.shared"):
                sh = ffn_fwd({k[7:]: v for k, v in p.items() if k.startswith("shared/")},
                             glu, x)
            return h + out.reshape(b, s, d) + sh, load

        return block


def leaf_shapes(cfg: DeepSeekV3Config) -> Dict[str, tuple]:
    """Every parameter's shape."""
    D, H, V = cfg.hidden_size, cfg.num_attention_heads, cfg.vocab_size
    nope, rope, vd, rank = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
                            cfg.kv_lora_rank)
    fe, fs = cfg.moe_intermediate_size, cfg.n_shared_experts * cfg.moe_intermediate_size
    out = {"embed/w": (V, D), "final/norm": (D,), "head/w": (D, V)}
    for l in range(cfg.num_hidden_layers):
        p = f"L{l}/"
        out.update({p + "attn/norm": (D,), p + "attn/wq": (D, H, nope + rope),
                    p + "attn/wkva": (D, rank + rope), p + "attn/kv_norm": (rank,),
                    p + "attn/wkvb": (rank, H, nope + vd), p + "attn/wo": (H, vd, D)})
        if l < cfg.first_k_dense_replace:
            F = cfg.intermediate_size
            out.update({p + "ffn/norm": (D,), p + "ffn/w_gate": (D, F), p + "ffn/w_up": (D, F),
                        p + "ffn/w_down": (F, D)})
        else:
            n = cfg.experts_held
            out.update({p + "moe/norm": (D,), p + "moe/router": (D, cfg.n_routed_experts),
                        p + "experts/w_gate": (n, D, fe), p + "experts/w_up": (n, D, fe),
                        p + "experts/w_down": (n, fe, D),
                        p + "shared/w_gate": (D, fs), p + "shared/w_up": (D, fs),
                        p + "shared/w_down": (fs, D)})
    return out


def units(cfg: DeepSeekV3Config) -> List[tuple]:
    """``(layer, count, [(leaf, axis), ...])`` of every prunable layer, in
    network order."""
    shapes = leaf_shapes(cfg)
    out = []
    for l in range(cfg.num_hidden_layers):
        p = f"L{l}/"
        out.append((p + "attn", cfg.num_attention_heads,
                    [(p + "attn/wq", 1), (p + "attn/wkvb", 1), (p + "attn/wo", 0)]))
        for name, lead in (("ffn", 0), ("shared", 0), ("experts", 1)):
            if p + f"{name}/w_gate" in shapes:
                out.append((p + name, shapes[p + f"{name}/w_down"][lead],
                            [(p + f"{name}/w_gate", lead + 1), (p + f"{name}/w_up", lead + 1),
                             (p + f"{name}/w_down", lead)]))
    return out


def matmuls(cfg: DeepSeekV3Config, shapes) -> List[Tuple[float, int, int]]:
    """``(M per sample, K, N)`` of every matmul of one sequence's forward
    pass at the widths ``shapes`` hold: attention's scores over every pair
    of positions (the causal mask is applied to a full product), the head
    over ``s - 1`` positions, and each held expert at the balanced load of
    ``s * k / n_routed_experts`` tokens."""
    S, D = cfg.seq_len, cfg.hidden_size
    nope, rope, vd, rank = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
                            cfg.kv_lora_rank)
    out: List[Tuple[float, int, int]] = []
    for l in range(cfg.num_hidden_layers):
        p = f"L{l}/"
        h = shapes[p + "attn/wq"][1]
        out += [(S, D, h * (nope + rope)), (S, D, rank + rope), (S, rank, h * (nope + vd)),
                (S * h, nope + rope, S), (S * h, S, vd), (S, h * vd, D)]
        if p + "ffn/w_gate" in shapes:
            F = shapes[p + "ffn/w_gate"][1]
            out += [(S, D, F), (S, D, F), (S, F, D)]
            continue
        n, _, fe = shapes[p + "experts/w_gate"]
        rows = S * cfg.num_experts_per_tok / cfg.n_routed_experts * n
        fs = shapes[p + "shared/w_gate"][1]
        out += [(S, D, cfg.n_routed_experts), (rows, D, fe), (rows, D, fe), (rows, fe, D),
                (S, D, fs), (S, D, fs), (S, fs, D)]
    out.append((S - 1, D, shapes["head/w"][1]))
    return out
