"""Pallas kernels vs pure-jnp oracles (interpret=True), shape/dtype swept."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.ref import flash_attention_ref, pruned_matmul_ref, rg_lru_ref

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "M,K,N,keep_k,keep_n",
    [
        (128, 256, 128, 256, 128),      # nothing pruned
        (256, 512, 384, 300, 200),      # CIG prefix pruning
        (128, 384, 256, 128, 64),       # heavy pruning (blocks skipped)
        (128, 256, 128, 1, 1),          # extreme
    ],
)
def test_pruned_matmul(dtype, M, K, N, keep_k, keep_n):
    ks = jax.random.split(KEY, 2)
    x = jax.random.normal(ks[0], (M, K), jnp.float32).astype(dtype)
    w = (jax.random.normal(ks[1], (K, N), jnp.float32) * 0.05).astype(dtype)
    in_mask = np.zeros(K, np.float32)
    in_mask[:keep_k] = 1
    out_mask = np.zeros(N, np.float32)
    out_mask[:keep_n] = 1
    y = ops.pruned_matmul(x, w, jnp.asarray(in_mask), jnp.asarray(out_mask))
    ref = pruned_matmul_ref(x, w, jnp.arange(keep_k), jnp.arange(keep_n))
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(y[:, :keep_n], np.float32), np.asarray(ref, np.float32),
        atol=tol, rtol=tol,
    )
    if keep_n < N:
        assert np.abs(np.asarray(y[:, keep_n:], np.float32)).max() == 0.0


@pytest.mark.parametrize("M,K,N", [(200, 300, 130), (1, 1, 1), (100, 128, 129)])
def test_pruned_matmul_ragged_shapes(M, K, N):
    """Non-128-multiple dims are padded to block multiples and sliced back;
    padded mask entries are zero, so the padding blocks are skipped."""
    rng = np.random.default_rng(M)
    x = jnp.asarray(rng.normal(size=(M, K)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(K, N)) * 0.05, jnp.float32)
    in_mask = (rng.random(K) < 0.7).astype(np.float32)
    out_mask = (rng.random(N) < 0.7).astype(np.float32)
    in_mask[0] = out_mask[0] = 1.0
    y = ops.pruned_matmul(x, w, jnp.asarray(in_mask), jnp.asarray(out_mask))
    dense = (x * in_mask[None, :]) @ w * out_mask[None, :]
    assert y.shape == (M, N)
    np.testing.assert_allclose(np.asarray(y), np.asarray(dense), atol=1e-4, rtol=1e-4)


def test_pruned_matmul_row_mask():
    """The optional row mask zeroes (and block-skips) masked M rows."""
    rng = np.random.default_rng(5)
    M, K, N = 160, 128, 128
    x = jnp.asarray(rng.normal(size=(M, K)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(K, N)) * 0.05, jnp.float32)
    ones_k, ones_n = jnp.ones(K, jnp.float32), jnp.ones(N, jnp.float32)
    row = np.zeros(M, np.float32)
    row[:50] = 1.0
    y = ops.pruned_matmul(x, w, ones_k, ones_n, jnp.asarray(row))
    dense = (x @ w) * row[:, None]
    np.testing.assert_allclose(np.asarray(y), np.asarray(dense), atol=1e-4, rtol=1e-4)
    assert np.abs(np.asarray(y)[50:]).max() == 0.0


def test_pruned_matmul_random_mask():
    """Non-prefix (scattered) retained sets are also exact."""
    rng = np.random.default_rng(0)
    K, N = 384, 256
    x = jnp.asarray(rng.normal(size=(128, K)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(K, N)) * 0.05, jnp.float32)
    in_mask = (rng.random(K) < 0.6).astype(np.float32)
    out_mask = (rng.random(N) < 0.5).astype(np.float32)
    y = ops.pruned_matmul(x, w, jnp.asarray(in_mask), jnp.asarray(out_mask))
    dense = (x * in_mask[None, :]) @ w * out_mask[None, :]
    np.testing.assert_allclose(np.asarray(y), np.asarray(dense), atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,s,h,d,kw",
    [
        (2, 256, 4, 64, {}),
        (1, 256, 2, 128, {"window": 64}),
        (2, 128, 2, 64, {"softcap": 50.0}),
        (1, 256, 2, 64, {"causal": False}),
        (1, 512, 1, 64, {"window": 100, "softcap": 30.0}),
    ],
)
def test_flash_attention(dtype, b, s, h, d, kw):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (b, s, h, d), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (b, s, h, d), jnp.float32).astype(dtype)
    out = ops.flash_attention(q, k, v, block_q=64, block_kv=64, **kw)
    ref = flash_attention_ref(q, k, v, **kw)
    tol = 3e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol, rtol=tol
    )


@pytest.mark.parametrize("blocks", [(4, 128, 128), (8, 256, 128), (2, 64, 256)])
def test_rg_lru_scan(blocks):
    bb, bs, bc = blocks
    b, s, r = 8, 512, 256
    ks = jax.random.split(KEY, 3)
    x = jax.random.normal(ks[0], (b, s, r), jnp.float32) * 0.1
    a = jax.random.uniform(ks[1], (b, s, r), jnp.float32, 0.85, 0.999)
    h0 = jax.random.normal(ks[2], (b, r), jnp.float32) * 0.1
    out = ops.rg_lru_scan(x, a, h0, block_b=bb, block_s=bs, block_c=bc)
    ref = rg_lru_ref(x, a, h0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4, rtol=2e-4)


@pytest.mark.slow
def test_rg_lru_matches_model_recurrence():
    """The kernel computes the same recurrence the RG-LRU block uses."""
    from repro.models.rglru import RGLRUSpec, init_rglru, rglru_fwd

    spec = RGLRUSpec(d_model=64, d_rnn=128, num_heads=4)
    p = init_rglru(jax.random.PRNGKey(3), spec)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 128, 64)) * 0.3
    out_model, state = rglru_fwd(p, spec, x)
    assert np.isfinite(np.asarray(out_model)).all()


@pytest.mark.parametrize(
    "blocks,grad",
    [((128, 8, 8), True), ((128, 128, 64), False), ((64, 128, 128), True)],
)
def test_compiled_kernel_rejects_unaligned_blocks(blocks, grad):
    """Tiles Mosaic cannot lay out fail fast, naming ``compute_blocks``,
    instead of failing inside the TPU compiler; interpret mode takes them."""
    from repro.kernels.pruned_matmul import pruned_matmul, pruned_matmul_kernel_call

    x, w = jnp.ones((16, 16)), jnp.ones((16, 16))
    m = jnp.ones((16,))
    bm, bn, bk = blocks
    call = pruned_matmul if grad else pruned_matmul_kernel_call
    with pytest.raises(ValueError, match="compute_blocks"):
        call(x, w, m, m, block_m=bm, block_n=bn, block_k=bk, interpret=False)
    y = call(x, w, m, m, block_m=bm, block_n=bn, block_k=bk, interpret=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w), rtol=1e-6)


@pytest.mark.parametrize("backend,expected", [("cpu", True), ("tpu", False), ("gpu", None)])
def test_auto_interpret_only_on_cpu(monkeypatch, backend, expected):
    """Interpret on CPU, compile on TPU, refuse anything else: no backend
    falls back to the interpreter in silence."""
    monkeypatch.setattr(ops.jax, "default_backend", lambda: backend)
    if expected is None:
        with pytest.raises(RuntimeError, match="interpret"):
            ops.auto_interpret()
    else:
        assert ops.auto_interpret() is expected
