"""The block-skip kernel compiles for a TPU v5e that is described, not attached.

Interpret mode (``test_kernels.py``, ``test_blockskip.py``) checks what the
kernel computes; only Mosaic, the TPU's kernel compiler, checks that its
tiling and layouts are legal on the chip.  The TPU compiler is installed on
CPU hosts too, so these cases compile the kernel at ``VGG16_CIFAR`` widths
(batch 32, 32 px) for one chip of a described ``v5e:2x2`` and need no device.

The topology is described inside a module fixture, never at import: only one
process may load the TPU library, and only the test worker that runs this
file should.  The persistent compilation cache is off for these tests: a
compile for a described chip can be written to it but not read back.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.pruned_matmul import pruned_matmul


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # no compiler logs on disk
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # no TPU compiler installed here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        cache_was_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
        compilation_cache.reset_cache()


def _masked_sq_loss(x, w, in_mask, out_mask):
    return jnp.sum(pruned_matmul(x, w, in_mask, out_mask) ** 2)


def _kernel_case(sds, kind, M, K, N):
    """(function, arguments, tpu_custom_calls expected) for one kernel case."""
    args = [sds((M, K)), sds((K, N)), sds((K,)), sds((N,))]
    if kind == "fwd":
        return pruned_matmul, args, 1
    grad = jax.grad(_masked_sq_loss, (0, 1))
    if kind == "grad":
        return grad, args, 3                        # forward, dX, dW
    return jax.vmap(grad), [sds((2,) + a.shape) for a in args], 3   # 2 rows


def _vgg16_step_case(sds):
    """The whole resident block-skip train step of one VGG16 worker: a
    4-step local-SGD scan, every conv and the head through the kernel."""
    from repro.core.worker import LocalTrainer
    from repro.models.cnn import VGG16_CIFAR, build_unit_space, init_cnn
    from repro.optim.group_lasso import group_size_sqrt

    params = jax.eval_shape(lambda: init_cnn(jax.random.PRNGKey(0), VGG16_CIFAR))
    zeros = {k: np.zeros(v.shape, np.float32) for k, v in params.items()}
    _, unit_map = build_unit_space(VGG16_CIFAR, zeros)
    trainer = LocalTrainer(VGG16_CIFAR, compute="block_skip", interpret=False)
    step = jax.vmap(trainer.make_resident_train(unit_map, 1e-4))
    W, n, steps, batch = 1, 128, 4, 32
    stack = {k: sds((W,) + v.shape) for k, v in params.items()}
    args = (
        stack,
        sds((W, n, 32, 32, 3)), sds((W, n), jnp.int32),
        sds((W, steps, batch), jnp.int32), sds((W, steps)),
        stack,
        {k: sds((W,)) for k in group_size_sqrt(zeros, unit_map)},
    )
    # 14 kernels forward (13 convs + head), 14 dW, 13 dX (the image needs none)
    return step, args, 41


@pytest.mark.parametrize(
    "case",
    [
        ("fwd", 32768, 27, 64),      # conv0: 32 px, 3 -> 64 channels
        ("fwd", 2048, 2304, 256),    # a 256-wide conv at 8 px
        ("fwd", 128, 4608, 512),     # a 512-wide conv at 2 px
        ("fwd", 32, 512, 10),        # the fc head
        ("grad", 2048, 2304, 256),
        ("vmap_grad", 2048, 2304, 256),
        ("vgg16_step",),
    ],
    ids=lambda c: "-".join(map(str, c)),
)
def test_pruned_matmul_compiles_for_v5e(one_chip, case):
    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    if case[0] == "vgg16_step":
        fn, args, kernels = _vgg16_step_case(sds)
    else:
        fn, args, kernels = _kernel_case(sds, *case)
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.as_text().count("tpu_custom_call") == kernels
    # one program must fit a v5e's 16 GB of HBM
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16 * 10**9


def test_kernel_carries_its_name(one_chip):
    """The kernel keeps the name ``pruned_matmul`` whatever function calls
    it: in the lowered custom call, and so in the compiled instruction a
    trace reports."""
    def sds(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    def step(x, w, in_mask, out_mask):
        return pruned_matmul(x, w, in_mask, out_mask) * 2.0

    lowered = jax.jit(step).lower(sds((256, 256)), sds((256, 256)), sds((256,)), sds((256,)))
    assert 'kernel_name = "pruned_matmul"' in lowered.as_text()
    assert "%pruned_matmul" in lowered.compile().as_text()


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_compile_cache_dir_rule(monkeypatch, tmp_path, env_dir):
    """``JAX_COMPILATION_CACHE_DIR`` wins and nothing is set in code;
    otherwise the cache sits at the fixed ``<checkout>/.jax_cache``."""
    from pathlib import Path

    from repro import compile_cache

    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(Path(compile_cache.__file__).resolve().parents[2] / ".jax_cache")
    else:
        want = str(tmp_path / env_dir)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    try:
        assert compile_cache.enable_compile_cache() == want
        set_in_code = jax.config.jax_compilation_cache_dir
        assert set_in_code == (before if env_dir else want)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
