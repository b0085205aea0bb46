"""Mask-aware block-skip compute path: kernel VJP, model lowering, fleet
equivalence, and the FLOPs-track-retention ledger.

Everything runs ``interpret=True`` on CPU (the kernels' CPU mode), so
the whole file is CI-runnable; on a TPU backend the same code compiles to
Mosaic.  The contracts pinned here:

* the ``pruned_matmul`` custom VJP matches the dense masked reference within
  1e-4 and produces *exactly* zero gradients on pruned in/out units (the
  resident fleet invariant: pruned coordinates stay exactly 0);
* ``cnn_apply(compute="block_skip")`` == the dense path on masked params, for
  VGG and ResNet wiring, forward and backward, including under ``vmap`` with
  per-row masks (one fleet program, heterogeneous retentions);
* a resident ``block_skip`` simulation is numerically equivalent to the
  dense masked engine (final-acc within 1e-3) while its executed-FLOPs
  ledger stays within 1.1x the ideal reconfigured cost at retention 0.25 and
  executes < 0.5x the blocks of retention 1.0.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.simulation import SimConfig, run_simulation
from repro.data.synthetic import SyntheticImageTask
from repro.kernels.pruned_matmul import pruned_matmul
from repro.models.cnn import (
    cnn_apply,
    cnn_block_compute,
    init_cnn,
    prunable_layer_names,
    resnet_config,
    vgg_config,
)

def _masks(rng, K, N, keep=0.5):
    im = (rng.random(K) < keep).astype(np.float32)
    om = (rng.random(N) < keep).astype(np.float32)
    im[0] = om[0] = 1.0  # never fully empty
    return jnp.asarray(im), jnp.asarray(om)


# ---------------------------------------------------------------------------
# kernel-level VJP
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "M,K,N,blocks",
    [
        (128, 256, 128, (128, 128, 128)),   # aligned
        (200, 300, 130, (128, 128, 128)),   # ragged (padded internally)
        (96, 144, 80, (32, 16, 16)),        # small tiles
    ],
)
def test_vjp_matches_dense_reference(M, K, N, blocks):
    rng = np.random.default_rng(M + K + N)
    x = jnp.asarray(rng.normal(size=(M, K)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(K, N)) * 0.05, jnp.float32)
    im, om = _masks(rng, K, N)
    bm, bn, bk = blocks

    def f(x_, w_):
        y = pruned_matmul(x_, w_, im, om, block_m=bm, block_n=bn, block_k=bk,
                          interpret=True)
        return jnp.sum(jnp.sin(y))

    def f_ref(x_, w_):
        return jnp.sum(jnp.sin((x_ * im[None, :]) @ w_ * om[None, :]))

    np.testing.assert_allclose(float(f(x, w)), float(f_ref(x, w)), rtol=1e-5)
    gx, gw = jax.grad(f, (0, 1))(x, w)
    rx, rw = jax.grad(f_ref, (0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(rx), atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(rw), atol=1e-4, rtol=1e-4)
    # pruned units get EXACT zeros, not small numbers
    assert np.abs(np.asarray(gx)[:, np.asarray(im) == 0]).max() == 0.0
    assert np.abs(np.asarray(gw)[np.asarray(im) == 0, :]).max() == 0.0
    assert np.abs(np.asarray(gw)[:, np.asarray(om) == 0]).max() == 0.0


def test_vjp_batched_vmap_per_row_masks():
    """One vmapped program serves heterogeneous retentions: per-row masks."""
    rng = np.random.default_rng(7)
    B, M, K, N = 3, 40, 96, 48
    xs = jnp.asarray(rng.normal(size=(B, M, K)), jnp.float32)
    ws = jnp.asarray(rng.normal(size=(B, K, N)) * 0.05, jnp.float32)
    ims = np.zeros((B, K), np.float32)
    oms = np.zeros((B, N), np.float32)
    for b, keep in enumerate((1.0, 0.5, 0.25)):   # prefix retentions
        ims[b, : max(1, int(K * keep))] = 1.0
        oms[b, : max(1, int(N * keep))] = 1.0
    ims, oms = jnp.asarray(ims), jnp.asarray(oms)

    f = jax.vmap(
        lambda a, b_, c, d: pruned_matmul(
            a, b_, c, d, block_m=32, block_n=16, block_k=16, interpret=True
        )
    )
    ref = jnp.einsum("bmk,bkn->bmn", xs * ims[:, None, :], ws) * oms[:, None, :]
    np.testing.assert_allclose(np.asarray(f(xs, ws, ims, oms)), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)
    gw = jax.grad(lambda w_: jnp.sum(f(xs, w_, ims, oms) ** 2))(ws)
    gr = jax.grad(lambda w_: jnp.sum(
        (jnp.einsum("bmk,bkn->bmn", xs * ims[:, None, :], w_) * oms[:, None, :]) ** 2
    ))(ws)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(gr), atol=1e-4, rtol=1e-4)
    assert np.abs(np.asarray(gw)[2][:, np.asarray(oms)[2] == 0]).max() == 0.0


# ---------------------------------------------------------------------------
# model-level lowering
# ---------------------------------------------------------------------------

def _prefix_masks(cfg, params, keep):
    out = {}
    for name in prunable_layer_names(cfg):
        n = params[f"{name}/bn_g"].shape[0]
        m = np.zeros(n, np.float32)
        m[: max(2, int(round(n * keep)))] = 1.0
        out[name] = m
    return out


def _mask_params(params, cfg, unit_masks):
    """Apply unit masks to params the way the fleet's mask stack does."""
    from repro.core.aggregation import coordinate_mask
    from repro.models.cnn import build_unit_space

    space, unit_map = build_unit_space(cfg, {k: np.asarray(v) for k, v in params.items()})
    index = {
        l.name: np.flatnonzero(unit_masks[l.name]).astype(np.int64)
        for l in space.layers
    }
    shapes = {k: v.shape for k, v in params.items()}
    return {
        k: jnp.asarray(v)
        * jnp.asarray(coordinate_mask(k, index, unit_map, shapes).astype(np.float32))
        for k, v in params.items()
    }


@pytest.mark.parametrize(
    "kind",
    ["vgg", pytest.param("resnet", marks=pytest.mark.slow)],
)
def test_cnn_apply_block_skip_matches_dense(kind):
    if kind == "vgg":
        cfg = vgg_config("t", [32, "M", 64], num_classes=10, image_size=8)
    else:
        cfg = resnet_config("t", 8, [(1, 8), (1, 16)], num_classes=10,
                            image_size=8, bottleneck=True)
    params = init_cnn(jax.random.PRNGKey(0), cfg)
    um = _prefix_masks(cfg, params, keep=0.5)
    pm = _mask_params(params, cfg, um)
    umj = {k: jnp.asarray(v) for k, v in um.items()}
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 8, 3))

    dense = cnn_apply(pm, cfg, x)
    bs = cnn_apply(pm, cfg, x, compute="block_skip", unit_masks=umj,
                   blocks=(128, 8, 8), interpret=True)
    np.testing.assert_allclose(np.asarray(bs), np.asarray(dense), atol=1e-4, rtol=1e-4)

    def loss(fn_params, compute):
        kw = ({"compute": "block_skip", "unit_masks": umj, "blocks": (128, 8, 8),
               "interpret": True} if compute == "block_skip" else {})
        return jnp.sum(jax.nn.log_softmax(cnn_apply(fn_params, cfg, x, **kw)))

    gb = jax.grad(lambda p: loss(p, "block_skip"))(pm)
    gd = jax.grad(lambda p: loss(p, "dense"))(pm)
    for k in gb:
        np.testing.assert_allclose(np.asarray(gb[k]), np.asarray(gd[k]),
                                   atol=1e-4, rtol=1e-4, err_msg=k)


# ---------------------------------------------------------------------------
# fleet-level equivalence + the FLOPs ledger
# ---------------------------------------------------------------------------

def _sim(compute, rate):
    cnn = vgg_config("t", [32, "M", 64], num_classes=10, image_size=8)
    task = SyntheticImageTask(num_classes=10, image_size=8, train_size=64,
                              test_size=64, seed=0)
    return run_simulation(SimConfig(
        method="adaptcl", engine="masked", compute=compute,
        compute_blocks=(128, 8, 8), importance="index",
        rounds=3, prune_interval=1, num_workers=2, batch_size=8,
        local_epochs=1.0, cnn=cnn, task=task, eval_every=3,
        fixed_pruned_rates=[[rate] * 2, [0.0] * 2, [0.0] * 2], seed=3,
    ))


@pytest.fixture(scope="module")
def sims():
    # rate 0.74 realizes retention ~0.25 under the index-prefix importance
    return _sim("dense", 0.74), _sim("block_skip", 0.74)


@pytest.mark.slow
def test_engine_equivalence_dense_vs_block_skip(sims):
    dense, bs = sims
    assert abs(dense.final_acc - bs.final_acc) <= 1e-3
    for k in dense.global_params:
        np.testing.assert_allclose(bs.global_params[k], dense.global_params[k],
                                   atol=1e-4, err_msg=k)
    assert bs.compute == "block_skip" and dense.compute == "dense"
    assert bs.compute_interpret and not dense.compute_interpret   # CPU backend
    assert bs.recompiles == dense.recompiles  # block-skip adds no shapes


@pytest.mark.slow
def test_flops_executed_tracks_retention(sims):
    dense, bs = sims
    assert 0.2 < np.mean(bs.retentions) < 0.3   # the rate landed where tuned
    # dense masked programs execute the base shapes -> executed > ideal
    assert dense.flops_executed > 1.2 * dense.flops_ideal
    # block_skip reports <= 1.1x the reconfigured ideal at retention ~0.25
    assert bs.flops_executed <= 1.1 * bs.flops_ideal
    assert bs.flops_ideal == dense.flops_ideal  # same schedule, same sub-models
    assert bs.blocks_executed > 0


def test_retention_quarter_executes_under_half_the_blocks():
    """The bench claim, host-side: prefix masks at retention 0.25 execute
    < 0.5x the kernel grid cells of retention 1.0 (per image)."""
    cfg = vgg_config("t", [32, "M", 64], num_classes=10, image_size=8)
    params = init_cnn(jax.random.PRNGKey(0), cfg)
    full = cnn_block_compute(cfg, _prefix_masks(cfg, params, 1.0), (128, 8, 8))
    quarter = cnn_block_compute(cfg, _prefix_masks(cfg, params, 0.25), (128, 8, 8))
    assert quarter["blocks"] < 0.5 * full["blocks"]
    assert full["blocks"] == full["blocks_total"]   # nothing skipped at 1.0
