"""The DeepSeek-V3-type family at a tiny size on seeded random weights: the
program's MLA and held-share expert layers against the benchmark's plain
reference (``bench/models/deepseek_v3.py``), the held shares against the
uncut layer, dropless routing, the correction bias, and pruned units."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.models import family
from repro.models.deepseek_v3 import DeepSeekV3Config
from repro.models.mla import MLASpec, mla_fwd
from repro.models.moe import HeldMoESpec, held_moe_fwd, sigmoid_route

REF = family("deepseek_v3")
HIGHEST = jax.lax.Precision.HIGHEST
TINY = dict(hidden_size=32, num_hidden_layers=3, num_attention_heads=4, qk_nope_head_dim=8,
            qk_rope_head_dim=4, v_head_dim=8, kv_lora_rank=16, intermediate_size=24,
            moe_intermediate_size=8, n_routed_experts=16, num_experts_per_tok=3,
            experts_held=4, n_shared_experts=2, vocab_size=300,
            seq_len=12, routed_scaling_factor=2.448, norm_topk_prob=True, rms_norm_eps=1e-6,
            rope_theta=1e6, init_std=0.2)


def _model(**kw):
    return dict(TINY, first_k_dense_replace=1, **kw)


def _params(model, seed=0):
    return REF.init_params(model, seed)


def _x(model, b=2, seed=1):
    ids = np.random.default_rng(seed).integers(0, model["vocab_size"], (b, model["seq_len"]))
    return jnp.asarray(np.stack([ids // 128, ids % 128], -1), jnp.int32)


def _tokens(d, n=40, seed=2):
    return jnp.asarray(np.random.default_rng(seed).normal(size=(n, d)), jnp.float32)


def _moe_params(p, layer=1):
    pre = f"L{layer}/"
    return {"router": p[pre + "moe/router"], **{w: p[f"{pre}experts/{w}"]
                                                for w in ("w_gate", "w_up", "w_down")}}


def _spec(model, first=0, held=None):
    return HeldMoESpec(model["n_routed_experts"], model["num_experts_per_tok"], first,
                       held or model["experts_held"], model["routed_scaling_factor"],
                       model["norm_topk_prob"])


def test_mla_matches_reference():
    model = _model()
    p = _params(model)
    x = _tokens(model["hidden_size"], 2 * model["seq_len"]).reshape(2, model["seq_len"], -1)
    a = {k[len("L0/attn/"):]: v for k, v in p.items() if k.startswith("L0/attn/")}
    spec = MLASpec(model["hidden_size"], model["num_attention_heads"], model["qk_nope_head_dim"],
                   model["qk_rope_head_dim"], model["v_head_dim"], model["kv_lora_rank"])
    got = mla_fwd(a, spec, x, precision=HIGHEST)
    want = REF._mla(p, "L0/attn/", model, x, HIGHEST)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_held_moe_matches_reference():
    model = _model()
    p = _params(model)
    x = _tokens(model["hidden_size"])
    with jax.default_matmul_precision("highest"):
        got, load = held_moe_fwd(_moe_params(p), _spec(model), x,
                                 jnp.zeros(model["n_routed_experts"]))
    want = REF._experts(p, "L1/", model, x, HIGHEST)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
    idx, _ = sigmoid_route(x, p["L1/moe/router"], jnp.zeros(model["n_routed_experts"]),
                           _spec(model))
    assert np.asarray(load).tolist() == [int((np.asarray(idx) == e).sum()) for e in range(4)]


def test_held_shares_sum_to_the_uncut_layer():
    # four ranks hold experts 0-3, 4-7, 8-11, 12-15; the shared experts,
    # computed alike on every rank, are counted once
    model = _model()
    uncut = _model(experts_held=model["n_routed_experts"])
    pu = _params(uncut)
    x = _tokens(model["hidden_size"])
    n = model["experts_held"]
    total = 0.0
    for r in range(model["n_routed_experts"] // n):
        share = {"router": pu["L1/moe/router"],
                 **{w: pu[f"L1/experts/{w}"][r * n:(r + 1) * n]
                    for w in ("w_gate", "w_up", "w_down")}}
        with jax.default_matmul_precision("highest"):
            out, _ = held_moe_fwd(share, _spec(model, first=r * n), x,
                                  jnp.zeros(model["n_routed_experts"]))
        total = total + out
    shared = REF._swiglu(x, pu["L1/shared/w_gate"], pu["L1/shared/w_up"], pu["L1/shared/w_down"],
                         HIGHEST)
    want = REF._experts(pu, "L1/", uncut, x, HIGHEST) + shared
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_no_token_is_dropped_when_every_token_routes_to_one_held_expert():
    model = _model()
    p = _params(model)
    x = _tokens(model["hidden_size"], n=64)
    bias = jnp.zeros(model["n_routed_experts"]).at[2].set(100.0)   # everyone picks expert 2
    with jax.default_matmul_precision("highest"):
        out, load = held_moe_fwd(_moe_params(p), _spec(model), x, bias)
    assert int(load[2]) == 64
    # every token's expert-2 part, computed densely
    idx, g = sigmoid_route(x, p["L1/moe/router"], bias, _spec(model))
    w = np.zeros((64, 4))
    for e in range(4):
        w[:, e] = np.where(np.asarray(idx) == e, np.asarray(g), 0.0).sum(-1)
    assert (w[:, 2] > 0).all()
    want = sum(w[:, e:e + 1] * np.asarray(REF._swiglu(
        x, *(p[f"L1/experts/{k}"][e] for k in ("w_gate", "w_up", "w_down")), HIGHEST))
        for e in range(4))
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5, atol=1e-5)


def test_correction_bias_moves_the_choice_not_the_weights():
    model = _model()
    p = _params(model)
    x = _tokens(model["hidden_size"], n=64)
    spec = _spec(model)
    idx0, _ = sigmoid_route(x, p["L1/moe/router"], jnp.zeros(16), spec)
    bias = jnp.zeros(16).at[5].set(2.0)
    idx, g = sigmoid_route(x, p["L1/moe/router"], bias, spec)
    assert (np.asarray(idx) == 5).any(axis=1).all() and not np.array_equal(idx, idx0)
    s = jax.nn.sigmoid(jnp.dot(x, p["L1/moe/router"], precision=HIGHEST))
    chosen = jnp.take_along_axis(s, idx, axis=1)
    np.testing.assert_allclose(np.asarray(g), np.asarray(
        model["routed_scaling_factor"] * chosen / chosen.sum(1, keepdims=True)), rtol=1e-6)


@pytest.mark.parametrize("layer,unit", [("L1/attn", 2), ("L0/ffn", 7), ("L1/shared", 3),
                                        ("L2/experts", 5)])
def test_a_pruned_unit_contributes_nothing(layer, unit):
    """Zeroing a unit's slices gives the model with that unit removed."""
    cfg = DeepSeekV3Config("tiny", **TINY)
    p = cfg.init(jax.random.PRNGKey(3))
    _, unit_map = cfg.unit_space(p)
    owned = [(path, ax) for path, entries in unit_map.items() for l, ax in entries if l == layer]
    masked = dict(p)
    sliced = dict(p)
    for path, ax in owned:
        keep = np.delete(np.arange(p[path].shape[ax]), unit)
        v = np.ones(p[path].shape[ax], np.float32)
        v[unit] = 0.0
        shape = [1] * p[path].ndim
        shape[ax] = -1
        masked[path] = p[path] * v.reshape(shape)
        sliced[path] = jnp.take(p[path], keep, axis=ax)
    small = dataclasses.replace(cfg, num_attention_heads=3) if layer.endswith("attn") else cfg
    x = _x(TINY)
    with jax.default_matmul_precision("highest"):
        a = cfg.apply(masked, x)["lp"]
        b = small.apply(sliced, x)["lp"]
        full = cfg.apply(p, x)["lp"]
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)
    assert not np.allclose(np.asarray(a), np.asarray(full))


def test_the_model_matches_reference():
    cfg = DeepSeekV3Config("tiny", **TINY)
    model = _model()
    p = REF.init_params(model, 4)
    q = cfg.init(jax.random.PRNGKey(4))
    assert all(np.array_equal(p[k], q[k]) for k in p) and sorted(p) == sorted(q)
    x = _x(TINY)
    with jax.default_matmul_precision("highest"):
        got = cfg.apply(q, x)
    want = REF.forward(p, model, x)
    np.testing.assert_allclose(np.asarray(got["lp"]), np.asarray(want[..., 0]), rtol=1e-5,
                               atol=1e-5)
    assert cfg.loss(got, None) == pytest.approx(float(REF.loss(want, None)), rel=1e-6)


def test_cig_bnscalor_is_refused_for_want_of_a_per_unit_scale():
    from repro.core.simulation import SimConfig, run_simulation

    sim = SimConfig(model=DeepSeekV3Config("tiny", **TINY), importance="cig_bnscalor",
                    engine="fused", num_workers=2)
    with pytest.raises(ValueError, match="no per-unit scale"):
        run_simulation(sim)


def _poisoned_ragged_dot(real):
    """``lax.ragged_dot`` with the rows past the groups' sum of its output,
    and of its transpose for the left operand, set to NaN: what the TPU
    kernel leaves there is undefined."""

    def poison(a, group_sizes):
        return jnp.where((jnp.arange(a.shape[0]) < group_sizes.sum())[:, None], a, jnp.nan)

    def ragged_dot(lhs, rhs, group_sizes, **kw):
        @jax.custom_vjp
        def f(lhs, rhs):
            return poison(real(lhs, rhs, group_sizes, **kw), group_sizes)

        def fwd(lhs, rhs):
            return f(lhs, rhs), (lhs, rhs)

        def bwd(res, g):
            dl, dr = jax.vjp(lambda a, b: real(a, b, group_sizes, **kw), *res)[1](g)
            return poison(dl, group_sizes), dr

        f.defvjp(fwd, bwd)
        return f(lhs, rhs)

    return ragged_dot


def test_rows_past_the_groups_stay_out_of_the_output_and_the_gradient(monkeypatch):
    """Most of the grouped matmuls' rows belong to no held expert; whatever
    the kernel leaves in them (NaN here) reaches neither the layer's output
    nor any gradient, which equal the dense reference's."""
    model = _model()
    p = _params(model)
    x = _tokens(model["hidden_size"])
    mp = _moe_params(p)
    bias = jnp.zeros(model["n_routed_experts"])
    monkeypatch.setattr(jax.lax, "ragged_dot", _poisoned_ragged_dot(jax.lax.ragged_dot))

    def prog(mp, x):
        return held_moe_fwd(mp, _spec(model), x, bias)[0]

    def ref(mp, x):
        q = dict(p, **{f"L1/experts/{w}": mp[w] for w in ("w_gate", "w_up", "w_down")})
        q["L1/moe/router"] = mp["router"]
        return REF._experts(q, "L1/", model, x, HIGHEST)

    ct = _tokens(model["hidden_size"], seed=3)

    @functools.partial(jax.jit, static_argnums=2)
    def out_and_grads(mp, x, f):
        out, vjp = jax.vjp(f, mp, x)
        return out, vjp(ct)

    with jax.default_matmul_precision("highest"):
        out, grads = out_and_grads(mp, x, prog)
    _, want = out_and_grads(mp, x, ref)
    assert int(held_moe_fwd(mp, _spec(model), x, bias)[1].sum()) < x.shape[0] * 3
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref(mp, x)), rtol=1e-5, atol=1e-5)
    for k in ("router", "w_gate", "w_up", "w_down"):
        np.testing.assert_allclose(np.asarray(grads[0][k]), np.asarray(want[0][k]),
                                   rtol=1e-4, atol=1e-5, err_msg=k)
    np.testing.assert_allclose(np.asarray(grads[1]), np.asarray(want[1]), rtol=1e-4, atol=1e-5)
