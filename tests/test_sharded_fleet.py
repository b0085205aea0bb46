"""Mesh-sharded fleet contracts (fused sync engine over a device mesh).

Pins down, on an 8-virtual-CPU-device mesh (``tests/conftest.py`` forces
``--xla_force_host_platform_device_count=8``):

  * sharded-fused == fused == sequential — final acc within 1e-3,
    ``prune_events`` BIT-identical, identical scenario event streams and
    channel draws (``update_times`` exact), for every mesh size that
    divides W, including under sampling / dropout / churn and the
    device-scored l1/taylor importance criteria;
  * the degenerate 1-device mesh is exactly the no-mesh engine;
  * host-dispatch economics stay O(R / round_fusion) FLAT in device count
    — sharding multiplies devices, not launches;
  * ``SimResult`` records the mesh (``n_devices`` / ``fleet_axis_size`` /
    ``shard_spec``), defaulting to 1/1/None on single-device runs;
  * the global -> (shard, local) index algebra behind shard-aware cohort
    gathers (``fleet.global_to_shard_local``, ``scenario.shard_cohorts``,
    ``bucket_rows(multiple=)``) and the bounds checks that keep a raw
    device ``take`` from silently clamping out-of-shard rows;
  * two-tier aggregation (per-shard partial reduce + global psum) matches
    the single-device reduction on real stacks;
  * unsupported-config guards: mesh requires the fused sync engine, a
    divisible W, and a fleet axis the mesh actually has.
"""
import numpy as np
import pytest

from repro.core.aggregation import (
    aggregate_by_unit_stacked_jnp,
    aggregate_by_worker_stacked_jnp,
)
from repro.core.fleet import (
    bucket_rows,
    gather_stack_rows,
    global_to_shard_local,
    scatter_stack_rows,
)
from repro.core.scenario import ScenarioConfig, shard_cohorts
from repro.core.simulation import SimConfig, run_simulation
from repro.core.timing import HeterogeneityConfig
from repro.models.cnn import vgg_config

TINY = vgg_config("vgg_tiny_fused", [8, "M", 16], num_classes=4, image_size=8)


def _sim(engine, mesh=None, **kw):
    base = dict(
        method="adaptcl",
        engine=engine,
        rounds=6,
        prune_interval=2,
        num_workers=8,          # divides every mesh size we build (1..8)
        batch_size=16,
        cnn=TINY,
        het=HeterogeneityConfig(num_workers=8, sigma=3.0),
        eval_every=2,
        seed=5,
    )
    base.update(kw)
    return run_simulation(SimConfig(mesh=mesh, **base))


def _mesh(n_dev):
    from repro.launch.mesh import make_fleet_mesh

    return make_fleet_mesh(n_dev)


def _assert_equivalent(ref, sharded):
    assert abs(ref.final_acc - sharded.final_acc) <= 1e-3
    assert ref.scenario_rounds == sharded.scenario_rounds
    assert ref.prune_events == sharded.prune_events
    np.testing.assert_allclose(
        np.array(ref.update_times), np.array(sharded.update_times),
        rtol=0, atol=0, equal_nan=True,
    )
    assert ref.total_time == pytest.approx(sharded.total_time, abs=1e-9)


# ---------------------------------------------------------------------------
# equivalence: sharded-fused == fused == sequential
# ---------------------------------------------------------------------------

def test_sharded_matches_fused_and_sequential(eight_devices):
    seq = _sim("sequential")
    fus = _sim("fused")
    shd = _sim("fused", mesh=_mesh(8))
    _assert_equivalent(seq, shd)
    _assert_equivalent(fus, shd)
    assert len(shd.prune_events) > 0


def test_sharded_scenario_streams_identical(eight_devices):
    scen = ScenarioConfig(participation=0.8, dropout=0.2, churn=0.15, seed=2)
    fus = _sim("fused", scenario=scen)
    shd = _sim("fused", mesh=_mesh(4), scenario=scen)
    _assert_equivalent(fus, shd)
    assert len(shd.scenario_rounds) == 6


def test_one_device_mesh_is_the_no_mesh_engine(eight_devices):
    """Degenerate golden: a 1-device mesh runs the same program modulo the
    shard_map wrapper — everything the channel/scenario/prune layers see is
    exact, and the mesh is still recorded in the result."""
    ref = _sim("fused")
    one = _sim("fused", mesh=_mesh(1))
    _assert_equivalent(ref, one)
    assert one.n_devices == 1 and one.fleet_axis_size == 1
    assert one.shard_spec == "PartitionSpec('fleet')"
    assert ref.shard_spec is None


@pytest.mark.slow
@pytest.mark.parametrize("importance", ["l1", "taylor"])
def test_sharded_importance_criteria(importance, eight_devices):
    # l1/taylor scores are computed ON DEVICE inside the sharded scan; the
    # reductions are row-local, so sharding the row axis cannot reorder the
    # removal walk — retained sets stay bit-identical to the host path
    seq = _sim("sequential", importance=importance)
    shd = _sim("fused", mesh=_mesh(8), importance=importance)
    _assert_equivalent(seq, shd)


@pytest.mark.slow
def test_sharded_by_unit_aggregation(eight_devices):
    # by_unit divides AFTER both psum tiers (num and den reduce globally
    # before the ratio) — pinned against the sequential host reference
    seq = _sim("sequential", aggregation="by_unit")
    shd = _sim("fused", mesh=_mesh(8), aggregation="by_unit")
    _assert_equivalent(seq, shd)


@pytest.mark.slow
def test_sharded_dgc_and_regrow(eight_devices):
    # device DGC is all row-local math (per-row top-|.| over the shard's own
    # residual stacks) and regrow is a host boundary step — neither crosses
    # rows, so keep sets, payload clocks and grow events survive sharding
    # bit-for-bit
    from repro.core.simulation import RegrowConfig

    kw = dict(dgc_sparsity=0.5, regrow=RegrowConfig(interval=2, alpha0=0.3),
              eval_every=6)
    fus = _sim("fused", **kw)
    shd = _sim("fused", mesh=_mesh(4), **kw)
    _assert_equivalent(fus, shd)
    assert fus.comm_bytes == shd.comm_bytes


# ---------------------------------------------------------------------------
# host-dispatch economics: flat in device count
# ---------------------------------------------------------------------------

def test_dispatches_flat_in_device_count(eight_devices):
    ref = _sim("fused", eval_every=6)
    for n_dev in (2, 8):
        shd = _sim("fused", mesh=_mesh(n_dev), eval_every=6)
        # same chunking, same jitted-launch count: sharding multiplies
        # devices, never dispatches
        assert shd.fused_chunks == ref.fused_chunks
        assert shd.host_dispatches == ref.host_dispatches
        assert shd.host_roundtrips == 0


def test_simresult_records_the_mesh(eight_devices):
    ref = _sim("fused", rounds=2, eval_every=2)
    shd = _sim("fused", mesh=_mesh(4), rounds=2, eval_every=2)
    assert (ref.n_devices, ref.fleet_axis_size, ref.shard_spec) == (1, 1, None)
    assert shd.n_devices == 4
    assert shd.fleet_axis_size == 4
    assert shd.shard_spec == "PartitionSpec('fleet')"


def test_sharded_chunk_program_compiles_once(eight_devices, caplog):
    """Every chunk call after the first reuses the first compile: the carries
    a chunk returns replicated (the global model) start out replicated too,
    so no later call brings new input shardings that recompile in silence
    behind ``recompiles == 1``."""
    import logging

    import jax

    with jax.log_compiles(True), caplog.at_level(logging.WARNING):
        shd = _sim("fused", mesh=_mesh(4), eval_every=6)
    assert shd.fused_chunks > 1 and shd.recompiles == 1
    chunk_compiles = [r for r in caplog.records
                      if "compilation of jit(chunk)" in r.getMessage()]
    assert len(chunk_compiles) == 1


# ---------------------------------------------------------------------------
# global -> (shard, local) index algebra
# ---------------------------------------------------------------------------

def test_global_to_shard_local_mapping():
    shard, local = global_to_shard_local([0, 3, 4, 7], num_workers=8, num_shards=2)
    np.testing.assert_array_equal(shard, [0, 0, 1, 1])
    np.testing.assert_array_equal(local, [0, 3, 0, 3])
    # 1 shard: identity on locals
    shard, local = global_to_shard_local([5, 2], num_workers=8, num_shards=1)
    np.testing.assert_array_equal(shard, [0, 0])
    np.testing.assert_array_equal(local, [5, 2])
    with pytest.raises(ValueError, match="outside"):
        global_to_shard_local([8], num_workers=8, num_shards=2)
    with pytest.raises(ValueError, match="outside"):
        global_to_shard_local([-1], num_workers=8, num_shards=2)
    with pytest.raises(ValueError, match="divide"):
        global_to_shard_local([0], num_workers=6, num_shards=4)


def test_shard_cohorts_partitions_in_draw_order():
    cohort = [6, 1, 4, 3]   # a sampled cohort in draw order
    parts = shard_cohorts(cohort, num_workers=8, num_shards=2)
    assert len(parts) == 2
    np.testing.assert_array_equal(parts[0], [1, 3])   # slots 1,3 -> local
    np.testing.assert_array_equal(parts[1], [2, 0])   # slots 6,4 -> local
    # every slot lands exactly once
    total = sum(len(p) for p in parts)
    assert total == len(cohort)
    with pytest.raises(ValueError, match="outside"):
        shard_cohorts([9], num_workers=8, num_shards=2)


def test_bucket_rows_respects_shard_multiple():
    assert bucket_rows(3, 8) == 4                    # pow2, unchanged
    assert bucket_rows(3, 8, multiple=1) == 4
    assert bucket_rows(2, 8, multiple=8) == 8        # floored to shard count
    assert bucket_rows(5, 8, multiple=4) == 8        # pow2 >= pow2 divides
    assert bucket_rows(5, 12, multiple=3) == 9       # non-pow2 shards round up
    with pytest.raises(ValueError, match="divide"):
        bucket_rows(9, 10, multiple=4)               # cap itself non-divisible


def test_gather_scatter_reject_out_of_range_rows():
    import jax.numpy as jnp

    stacks = {"w": jnp.arange(12.0).reshape(4, 3)}
    sub = gather_stack_rows(stacks, np.array([2, 0]), num_rows=4)
    np.testing.assert_array_equal(np.asarray(sub["w"]), [[6, 7, 8], [0, 1, 2]])
    with pytest.raises(ValueError, match="GLOBAL"):
        gather_stack_rows(stacks, np.array([4]), num_rows=4)
    with pytest.raises(ValueError, match="GLOBAL"):
        scatter_stack_rows(stacks, np.array([-1]), sub, num_rows=4)


# ---------------------------------------------------------------------------
# two-tier aggregation: per-shard partial reduce + global psum
# ---------------------------------------------------------------------------

def test_two_tier_aggregation_matches_single_device(eight_devices):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.sharding.specs import fleet_sharding

    mesh = _mesh(4)
    rng = np.random.default_rng(7)
    W = 8
    stacks = {"a": rng.normal(size=(W, 3, 2)).astype(np.float32),
              "b": rng.normal(size=(W, 5)).astype(np.float32)}
    masks = {k: (rng.random(v.shape) > 0.3).astype(np.float32)
             for k, v in stacks.items()}
    weights = rng.random(W).astype(np.float32)
    submitters = (rng.random(W) > 0.2).astype(np.float32)

    ref_w = aggregate_by_worker_stacked_jnp(
        {k: jnp.asarray(v) for k, v in stacks.items()}, jnp.asarray(weights))
    ref_u = aggregate_by_unit_stacked_jnp(
        {k: jnp.asarray(v) for k, v in stacks.items()},
        {k: jnp.asarray(v) for k, v in masks.items()},
        jnp.asarray(submitters))

    sh = fleet_sharding(mesh)
    dstacks = {k: jax.device_put(v, sh) for k, v in stacks.items()}
    dmasks = {k: jax.device_put(v, sh) for k, v in masks.items()}

    two_w = jax.shard_map(
        lambda s, w: aggregate_by_worker_stacked_jnp(s, w, axis="fleet"),
        mesh=mesh, in_specs=(P("fleet"), P("fleet")), out_specs=P(),
        check_vma=False,
    )(dstacks, jax.device_put(weights, sh))
    two_u = jax.shard_map(
        lambda s, m, sub: aggregate_by_unit_stacked_jnp(s, m, sub, axis="fleet"),
        mesh=mesh, in_specs=(P("fleet"), P("fleet"), P("fleet")), out_specs=P(),
        check_vma=False,
    )(dstacks, dmasks, jax.device_put(submitters, sh))

    for k in stacks:
        np.testing.assert_allclose(
            np.asarray(two_w[k]), np.asarray(ref_w[k]), rtol=0, atol=1e-6)
        np.testing.assert_allclose(
            np.asarray(two_u[k]), np.asarray(ref_u[k]), rtol=0, atol=1e-6)


# ---------------------------------------------------------------------------
# unsupported-config guards
# ---------------------------------------------------------------------------

def test_mesh_requires_fused_sync_engine(eight_devices):
    with pytest.raises(ValueError, match="fused"):
        _sim("masked", mesh=_mesh(2), rounds=2)
    with pytest.raises(ValueError, match="fused"):
        _sim("fused", mesh=_mesh(2), method="fedasync_s", rounds=2)


def test_mesh_requires_divisible_fleet(eight_devices):
    with pytest.raises(ValueError, match="divide"):
        _sim("fused", mesh=_mesh(8), num_workers=5,
             het=HeterogeneityConfig(num_workers=5, sigma=3.0), rounds=2)


def test_mesh_requires_fleet_axis(eight_devices):
    import jax

    bad = jax.make_mesh((2,), ("data",))
    with pytest.raises(ValueError, match="fleet"):
        _sim("fused", mesh=bad, rounds=2)
