"""Fused round engine: whole synchronous rounds as on-device ``lax.scan`` chunks.

The resident masked engine (``core.fleet.FleetState``) already keeps the
fleet's ``[W, ...]`` stacks on device, but every round still pays a host
boundary: a jit dispatch for each train phase, a ``params_host`` pull, NumPy
float64 aggregation, host importance scoring and ``prune_to_budget``, and a
``refresh_masks`` rewrite — per round, per fleet.  This module removes that
boundary: ``SimConfig.engine = "fused"`` expresses the ENTIRE synchronous
round — masked broadcast-back (``theta_g[None] * M``), vmapped fleet
training, stacked aggregation, importance scoring, budget pruning, and
mask-row refresh — in pure ``jnp`` over the resident stacks, and runs chunks
of rounds as a single ``jax.lax.scan`` device program.  R rounds execute in
``O(R / round_fusion)`` host dispatches (``SimResult.host_dispatches``)
instead of ``O(R)``.

**Chunk boundaries.**  Newton pruned-rate learning (``core.pruned_rate``,
scalar host math over per-worker histories) stays on host — it is the
natural fusion boundary: chunks span the rounds BETWEEN prune-rate-learning
events (every ``prune_interval`` rounds for ``adaptcl``), capped at
``SimConfig.round_fusion`` when set.  Churn rounds also cut chunks (a slot
replacement swaps data shards and resets host bookkeeping); sampling and
dropout are pure participation masks and fuse freely.

**Engine-identical decisions.**  Everything the host path draws from RNG is
pre-drawn in the SAME stream order: scenario events come from
``ScenarioEngine.draw_all`` (events + churn shards, the dedicated scenario
stream), batch plans and channel-jitter multipliers are drawn per round in
the lazy loop's exact ``env.rng`` order during chunk pre-compute.  Pruning
replays host ``prune_to_budget`` exactly: removal ORDERS for the
data-independent criteria are host-exact integer permutations
(``masks.prune_order``, float64 scores + ``(score, layer, unit)``
tie-break), budgets are exact integer thresholds (``prune_budget_units``),
and the device greedy (``prune_presence_rows``) replays the same walk — so
given the same scores, the removed unit sets are bit-identical.  The
seed-derived criteria (``index``/``no_adjacent``/``no_identical``/
``no_constant``) therefore carry an UNCONDITIONAL bit-identity guarantee;
``cig_bnscalor``'s frozen scores are |BN gamma| of the trained global at
the freeze event, which differs across engines at float32-drift scale
(fused aggregates in f32 on device, the host paths in f64), so a near-tie
inside that drift could in principle reorder two units — the equivalence
tests pin index equality on real runs.  Data-dependent criteria
(``l1``/``taylor``, ``importance.DEVICE_METHODS``) are scored on device in
float32 with the same caveat.

The host recovers per-round ``GlobalIndex`` values lazily from the scan's
``[K, W, U]`` presence outputs — ONLY for payload/FLOPs accounting and the
channel model, after the chunk has already run.  Per-round aggregated
globals come back as stacked scan outputs, so ``eval_every`` never forces a
chunk split.

**Async fusion.**  The asynchronous schedulers (``fedasync_s`` / ``ssp_s``
/ ``dcasgd_s``) fuse too (``run_async_fused``): the whole discrete-event
run is pre-simulated on host into a ``scenario.AsyncEventPlan``
(``simulation._plan_async_events`` — possible because async workers never
prune, so event timing is independent of trained parameter values), and
chunks of ``round_fusion`` window batches then run as ONE ``lax.scan``
program each.  Inside the scan the pending-commit queue is a device array:
each batch's events arrive in heap PUSH order with split-float64 finish
keys, ``async_pop_perm`` (a ``lexsort`` — sorted finish-times replacing the
host heap) re-derives the commit order including the host heap's
``(time, worker)`` tie-break, and an inner scan walks the commits through
``aggregation.async_commit_jnp`` merges, integer staleness counters
(``version - fetched_ver``), dropout gating, and masked refetch
(``fleet.refetch_rows_jnp``).  A per-chunk runtime check compares the
device pop order and staleness integers against the plan and raises on
divergence, so commit schedules are bit-identical to the resident engine
by construction — E events run in ``O(E / round_fusion)`` host dispatches.

**DGC on device.**  ``dgc_sparsity > 0`` runs INSIDE the scan:
``aggregation.dgc_compress_jnp`` top-|.|-compresses the ``[W, ...]`` delta
stacks (delta = trained params minus the masked broadcast-back) with the
residual accumulators carried in the scan state, and aggregation consumes
``theta_g[None] * M + committed``.  Keep sets are bit-identical to the host
compressor (``simulation._dgc_compress_stacked``): both compute keep
budgets with the same float32 rounding and threshold the same float32
values, mirroring how ``prune_order`` makes pruning host-exact.  Realized
per-round kept/total counts come back as ``[K, W]`` scan outputs, so the
payload factors feeding the channel model are the host path's exact
integers.

**Mask regrowth.**  FedDST-style readjustment (``SimConfig.regrow``) also
cuts chunks: a regrow round always opens a chunk, the shared host step
(``simulation._regrow_step``) rewrites the global indices at that boundary
(shrink by global weight magnitude, grow back by gradient magnitude — one
extra cached jit signature for the gradient), and the next chunk simply
starts from the readjusted presence rows.  The chunk program is unchanged,
so regrow costs zero recompiles.

Out of scope (see ROADMAP): participation-sized sub-stack gathering inside
a scan (fused rounds compute all W rows with validity masks), and the
``block_skip`` compute path under the scan (interpret-mode Pallas inside
``lax.scan`` is untested off-TPU).
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models.cnn import cnn_flops_from_shapes, extract_bn_scales
from repro.sharding.specs import fleet_sharding

from repro.optim.group_lasso import group_size_sqrt_from_shapes

from .aggregation import (
    aggregate_by_unit_stacked_jnp,
    aggregate_by_worker_stacked_jnp,
    async_commit_jnp,
    async_health_step_jnp,
    delta_norms_jnp,
    dgc_compress_jnp,
    extract_subparams,
    noise_key,
    robust_submission_step_jnp,
    roundtrip_total,
    subparam_shapes,
)
from .faults import fault_ledger
from .fleet import gl_factors_from_counts, masks_from_presence, refetch_rows_jnp
from .importance import (
    DEVICE_METHODS,
    METHODS,
    STATIC_METHODS,
    ImportanceContext,
    l1_scores_jnp,
    taylor_scores_jnp,
)
from .masks import (
    UnitFlat,
    flatten_unit_space,
    full_index,
    index_from_presence,
    presence_from_index,
    prune_budget_units,
    prune_order,
    prune_presence_rows,
    retention,
    similarity,
)
from .pruned_rate import WorkerHistory, learn_pruned_rates
from .scenario import ScenarioEngine, ScenarioPlan
from .spans import span
from .timing import heterogeneity_from_times
from .worker import make_batch_plan, plan_steps, stack_batch_plans

__all__ = [
    "run_sync_fused",
    "run_async_fused",
    "async_pop_perm",
    "split_time_keys",
    "validate_fused_config",
]


def validate_fused_config(sim) -> None:
    """Reject configurations the fused engine does not express on device."""
    if sim.compute != "dense":
        raise ValueError(
            "engine='fused' supports compute='dense' only — the block_skip "
            "interpret-mode kernel inside lax.scan is out of scope off-TPU"
        )
    supported = STATIC_METHODS | DEVICE_METHODS
    if sim.importance not in supported:
        raise ValueError(
            f"engine='fused' supports importance criteria {sorted(supported)}; "
            f"{sim.importance!r} needs host-side statistics (use "
            "engine='masked')"
        )
    mesh = getattr(sim, "mesh", None)
    if mesh is not None:
        axis = sim.fleet_axis
        if axis not in mesh.shape:
            raise ValueError(
                f"SimConfig.mesh axes {tuple(mesh.shape)} have no fleet "
                f"axis {axis!r} (SimConfig.fleet_axis)"
            )
        n_dev = mesh.shape[axis]
        if sim.num_workers % n_dev:
            raise ValueError(
                f"num_workers={sim.num_workers} does not divide over the "
                f"{n_dev}-way {axis!r} mesh axis (W = n_dev x W_local)"
            )


def _static_orders(sim, env, flat: UnitFlat, cig_scores, prune_round_count):
    """Host-exact ``[W, U]`` removal orders for the data-independent
    criteria (``None`` while CIG scores are not yet frozen — unused then,
    because no prune can fire before the first learning event)."""
    W = sim.num_workers
    name = sim.importance
    if name == "cig_bnscalor":
        if cig_scores is None:
            return None
        return np.tile(prune_order(cig_scores, flat), (W, 1))
    ctx = dict(unit_counts=env.space.unit_counts, round=prune_round_count,
               seed=sim.seed)
    if name != "no_identical":    # one shared order across workers
        scores = METHODS[name](ImportanceContext(**ctx))
        return np.tile(prune_order(scores, flat), (W, 1))
    rows = []
    for w in range(W):
        scores = METHODS[name](ImportanceContext(worker=w, **ctx))
        rows.append(prune_order(scores, flat))
    return np.stack(rows)


def _build_chunk_fn(trainer, unit_map, base_shapes, flat: UnitFlat, lam,
                    *, by_unit: bool, importance: str,
                    resident_momentum: bool, has_phase_b: bool,
                    dgc_sparsity: float = 0.0,
                    mesh=None, fleet_axis: str = "fleet",
                    robust=None, byz=None, corrupt_std=None,
                    channel: bool = False, noise_seed: int = 0,
                    fleet_w=None):
    """Build the jitted chunk program: ``lax.scan`` over K fused rounds.

    Carry: (param stacks, mask stacks, flat presence, global params,
    momentum stacks) — everything a round needs, so nothing touches the host
    between scan steps.  Per-round inputs arrive as ``[K, ...]`` tensors;
    per-round outputs (post-prune presence, post-aggregation global) come
    back stacked so the host can account payloads/clock and evaluate lazily.

    **Mesh-sharded fleet** (``mesh`` set): the SAME chunk body runs under
    ``shard_map`` over the ``fleet_axis`` mesh axis — each device scans its
    ``W_local = W / n_dev`` rows.  Everything in a round is row-local
    (masked broadcast-back of the replicated global, vmapped training,
    presence pruning, device importance scores), EXCEPT aggregation, which
    becomes the two-tier on-mesh collective
    (``aggregate_by_*_stacked_jnp(axis=...)``: per-shard partial reduce,
    then a global ``psum``), after which the new global is replicated on
    every shard again.  One jit dispatch still covers the whole chunk, so
    host dispatches stay O(R / round_fusion) while W scales with devices.

    Prune-order bit-identity under sharding: removal orders for the static
    criteria ship from host as ``[W, U]`` integer rows (importance scores
    gathered/computed on HOST at prune events — never trained params), and
    the device-scored criteria (l1/taylor) reduce within a row only — no
    cross-worker collective touches a score, so sharding the row axis
    cannot reorder a removal walk."""
    train_one = trainer.make_resident_train(unit_map, lam, carry_momentum=True)
    vm_train = jax.vmap(
        lambda p, m0, x, y, plan, valid, mask, gl:
            train_one(p, x, y, plan, valid, mask, gl, m0)
    )
    slices = {
        name: (int(flat.offsets[l]), int(flat.sizes[l]))
        for l, name in enumerate(flat.names)
    }

    def counts_of(presence):
        return {
            name: presence[:, off : off + sz].sum(axis=1)
            for name, (off, sz) in slices.items()
        }

    def device_scores(params, masks, presence, xs, ys, sizes):
        """Data-dependent importance in base coordinates over the stacks.

        Mirrors ``worker.local_unit_stats`` + the host METHODS: masked unit
        group norms (l1) / per-unit |sum g.w| on the first <=64 shard images
        (taylor), non-retained slots scattered to -inf."""
        if importance == "l1":
            sq: Dict[str, jnp.ndarray] = {}
            for path, entries in unit_map.items():
                arr = params.get(path)
                if arr is None:
                    continue
                for lname, axis in entries:
                    axes = tuple(
                        i for i in range(arr.ndim) if i not in (0, 1 + axis)
                    )
                    s = jnp.sum(jnp.square(arr.astype(jnp.float32)), axis=axes)
                    sq[lname] = sq.get(lname, 0.0) + s
            norms = {k: jnp.sqrt(jnp.maximum(v, 1e-12)) for k, v in sq.items()}
            return l1_scores_jnp(norms, flat.names, presence)
        # taylor: grads of the masked CE on each worker's first <=64 images
        nb = min(64, xs.shape[1])
        xb, yb = xs[:, :nb], ys[:, :nb]
        wv = (
            jnp.arange(nb)[None, :] < jnp.minimum(sizes, nb)[:, None]
        ).astype(jnp.float32)

        def ce_one(q, mask, x, y, v):
            qm = jax.tree.map(lambda w, m: w * m, q, mask)
            logp = jax.nn.log_softmax(trainer._masked_logits(qm, mask, x))
            pick = jnp.take_along_axis(logp, y[:, None], axis=1)[:, 0]
            return -(pick * v).sum() / jnp.maximum(v.sum(), 1.0)

        grads = jax.vmap(
            lambda q, mask, x, y, v: jax.grad(ce_one)(q, mask, x, y, v)
        )(params, masks, xb, yb, wv)
        gw: Dict[str, jnp.ndarray] = {}
        for path, entries in unit_map.items():
            if path not in grads:
                continue
            for lname, axis in entries:
                g, w_ = grads[path], params[path]
                axes = tuple(i for i in range(g.ndim) if i not in (0, 1 + axis))
                gw[lname] = gw.get(lname, 0.0) + jnp.abs(
                    jnp.sum(g * w_, axis=axes)
                )
        return taylor_scores_jnp(gw, flat.names, presence)

    use_dgc = dgc_sparsity > 0.0
    # robust submission path: byzantine transform + channel corruption +
    # clip/trim/quarantine, all in-scan via robust_submission_step_jnp — the
    # SAME function the masked loop calls per round.  Static config; the
    # quarantine health state rides the carry (full-fleet [W] rows,
    # replicated under the mesh — health is a fleet-wide order statistic).
    # NOTE: a lossy channel with corrupt=0 still routes through the robust
    # path — the commit multiplicity (drop/dup) reshapes the weights and the
    # all-lost-round wsum==0 guard must be the SAME code as the masked loop.
    robust_on = (byz is not None or corrupt_std is not None
                 or robust is not None or channel)
    quar_cfg = robust.quarantine if robust is not None else None

    # Device scopes (``jax.named_scope``) name each phase of a round in the
    # op names of the compiled program, so a profile's device ops sort into
    # broadcast / train (``worker.make_resident_train``) / importance /
    # prune / dgc / aggregate; they change HLO metadata only, not fusion.
    def chunk(params, momentum, presence, global_p, dgc_res, health, xs, ys,
              sizes, per_round, orders):
        with jax.named_scope("broadcast"):
            masks = masks_from_presence(presence, flat, unit_map, base_shapes)

        def body(carry, inp):
            params, masks, presence, global_p, momentum, dgc_res, health = carry
            # crash recovery at the round start, in-scan: rows flagged in
            # inp["recov"] re-enter with their last mask but restart
            # velocity/DGC residuals (they were accumulated against
            # pre-crash parameters).  All-zero on fault-free rounds, so the
            # compiled program is shared and the fault-free math unchanged.
            with jax.named_scope("broadcast"):
                if resident_momentum or use_dgc:
                    keep = 1.0 - inp["recov"]
                    if resident_momentum:
                        momentum = {
                            k: v * keep.reshape((-1,) + (1,) * (v.ndim - 1))
                            for k, v in momentum.items()
                        }
                    if use_dgc:
                        dgc_res = {
                            k: v * keep.reshape((-1,) + (1,) * (v.ndim - 1))
                            for k, v in dgc_res.items()
                        }
                # broadcast-back: masked scatter of the global into every row
                params = {k: global_p[k][None] * masks[k] for k in params}
                gl = gl_factors_from_counts(
                    counts_of(presence), unit_map, base_shapes
                )
                m0 = (momentum if resident_momentum
                      else jax.tree.map(jnp.zeros_like, params))
            params, m_out, _ = vm_train(
                params, m0, xs, ys, inp["plan_a"], inp["valid_a"], masks, gl
            )
            momentum = m_out if resident_momentum else momentum

            def prune_branch(op):
                params, masks, presence, momentum = op
                with jax.named_scope("importance"):
                    if importance in STATIC_METHODS:
                        ow = orders
                    else:
                        scores = device_scores(
                            params, masks, presence, xs, ys, sizes
                        )
                        # the tie-break ranks enter as a constant of the
                        # trace: the built program holds no device array
                        ow = jax.vmap(
                            lambda s: jnp.lexsort(
                                (jnp.asarray(flat.tiebreak), s)
                            ).astype(jnp.int32)
                        )(scores)
                with jax.named_scope("prune"):
                    pres2 = prune_presence_rows(presence, ow, inp["budgets"], flat)
                    masks2 = masks_from_presence(pres2, flat, unit_map, base_shapes)
                    params2 = {k: params[k] * masks2[k] for k in params}
                    mom2 = (
                        {k: momentum[k] * masks2[k] for k in momentum}
                        if resident_momentum else momentum
                    )
                if has_phase_b:
                    with jax.named_scope("train"):
                        gl2 = gl_factors_from_counts(
                            counts_of(pres2), unit_map, base_shapes
                        )
                        m0b = (mom2 if resident_momentum
                               else jax.tree.map(jnp.zeros_like, params2))
                        params2, m_b, _ = vm_train(
                            params2, m0b, xs, ys,
                            inp["plan_b"], inp["valid_b"], masks2, gl2,
                        )
                    mom2 = m_b if resident_momentum else mom2
                return params2, masks2, pres2, mom2

            params, masks, presence, momentum = jax.lax.cond(
                inp["prune_any"], prune_branch, lambda op: op,
                (params, masks, presence, momentum),
            )

            # submission boundary: DGC top-|.| delta compression on device.
            # Deltas are vs the masked broadcast-back; submitters-gated, so
            # dead padding rounds (submitters all 0) touch no residual.
            with jax.named_scope("dgc"):
                if use_dgc:
                    deltas = {
                        k: params[k] - global_p[k][None] * masks[k] for k in params
                    }
                    committed, dgc_res, kept_w, total_w = dgc_compress_jnp(
                        deltas, dgc_res, dgc_sparsity, masks, inp["submitters"]
                    )
                    agg_in = {
                        k: global_p[k][None] * masks[k] + committed[k]
                        for k in params
                    }
                else:
                    agg_in = params
                    kept_w = total_w = None

            with jax.named_scope("aggregate"):
                agg_axis = fleet_axis if mesh is not None else None
                quar_row = None
                if by_unit:
                    g_new = aggregate_by_unit_stacked_jnp(
                        agg_in, masks, inp["submitters"], axis=agg_axis
                    )
                elif robust_on:
                    # noise keys derive from the ROUND NUMBER in-scan via the
                    # same fold_in chain the masked loop runs eagerly — threefry
                    # is deterministic, so the streams are bit-identical.
                    byz_key = (
                        noise_key(noise_seed + 51721, inp["rnd"])
                        if byz is not None else None
                    )
                    cor_key = (
                        noise_key(noise_seed + 51722, inp["rnd"])
                        if corrupt_std is not None else None
                    )
                    g_new, st2, qu2, quar_row = robust_submission_step_jnp(
                        agg_in, masks, global_p, inp["mult"], inp["weights"],
                        inp["byz"] if byz is not None else None,
                        inp["corrupt"] if corrupt_std is not None else None,
                        byz_key, cor_key,
                        health.get("strikes"), health.get("quar"),
                        byz_mode=byz.mode if byz is not None else "sign_flip",
                        byz_scale=byz.scale if byz is not None else -10.0,
                        byz_noise_std=byz.noise_std if byz is not None else 1.0,
                        corrupt_std=corrupt_std if corrupt_std is not None else 10.0,
                        clip=robust.clip if robust is not None else None,
                        trim=robust.trim if robust is not None else 0.0,
                        quarantine=quar_cfg,
                        gate=inp["real"], axis=agg_axis, full_rows=fleet_w,
                    )
                    if quar_cfg is not None:
                        health = {"strikes": st2, "quar": qu2}
                else:
                    g_new = aggregate_by_worker_stacked_jnp(
                        agg_in, inp["weights"], axis=agg_axis
                    )
                # dead padding rounds (real=False) keep the global untouched, so
                # every chunk shares ONE [K]-shaped compiled program
                global_p = {
                    k: jnp.where(inp["real"], g_new[k].astype(jnp.float32),
                                 global_p[k])
                    for k in global_p
                }
            return (
                params, masks, presence, global_p, momentum, dgc_res, health
            ), (presence, global_p, kept_w, total_w, quar_row)

        carry0 = (params, masks, presence, global_p, momentum, dgc_res, health)
        (params, masks, presence, global_p, momentum, dgc_res, health), (
            pres_seq, glob_seq, kept_seq, total_seq, quar_seq
        ) = jax.lax.scan(body, carry0, per_round)
        return (params, momentum, presence, global_p, dgc_res, health,
                pres_seq, glob_seq, kept_seq, total_seq, quar_seq)

    if mesh is None:
        return jax.jit(chunk)

    # one lax.scan program PER SHARD: row-stacked args shard over the fleet
    # axis (dim 0 for state, dim 1 for [K, W, ...] per-round tensors), the
    # global and the per-round scalars replicate; outputs mirror that, with
    # the post-psum global (and its [K, ...] eval trail) replicated.
    fleet, rep = P(fleet_axis), P()
    per_round_specs = {
        "plan_a": P(None, fleet_axis), "valid_a": P(None, fleet_axis),
        "budgets": P(None, fleet_axis), "prune_any": rep, "real": rep,
        "weights": P(None, fleet_axis), "submitters": P(None, fleet_axis),
        "recov": P(None, fleet_axis),
    }
    if has_phase_b:
        per_round_specs["plan_b"] = P(None, fleet_axis)
        per_round_specs["valid_b"] = P(None, fleet_axis)
    # robust per-round rows shard like the other [K, W] tensors; the round
    # numbers (noise-key seeds) are scalars every shard needs — full-W noise
    # is generated per shard then row-sliced for bit-identity — so replicate.
    if robust_on:
        per_round_specs["mult"] = P(None, fleet_axis)
        per_round_specs["rnd"] = rep
    if byz is not None:
        per_round_specs["byz"] = P(None, fleet_axis)
    if corrupt_std is not None:
        per_round_specs["corrupt"] = P(None, fleet_axis)
    # kept/total [K, W] scan outputs shard like the presence trail; the DGC
    # residual stacks join the fleet-sharded state (all row-local math).
    # When DGC is off those slots are empty pytrees and the specs are inert.
    # Quarantine health state (and the quar trail) is a fleet-wide order
    # statistic computed on gathered norms — replicated [W] rows.
    kt = P(None, fleet_axis)
    return jax.jit(jax.shard_map(
        chunk, mesh=mesh,
        in_specs=(fleet, fleet, fleet, rep, fleet, rep, fleet, fleet, fleet,
                  per_round_specs, fleet),
        out_specs=(fleet, fleet, fleet, rep, fleet, rep, P(None, fleet_axis),
                   rep, kt, kt, rep),
        check_vma=False,
    ))


def run_sync_fused(sim, env):
    """Synchronous simulation with the fused round engine (see module doc).

    Mirrors ``simulation._run_sync`` decision-for-decision; the differences
    are WHERE things run (rounds on device in scan chunks, accounting on
    host after each chunk), never WHAT is computed.
    """
    from .simulation import (   # lazy: no import cycle
        _env_accuracy,
        _finalize,
        _regrow_round,
        _regrow_step,
        _skip_round_time,
    )

    # set-up, up to the first chunk's plan (the env's own is in
    # run_simulation's set-up span)
    with span("setup"):
        validate_fused_config(sim)
        W = sim.num_workers
        use_dgc = sim.dgc_sparsity > 0.0
        adapt = sim.method == "adaptcl"
        sparse = sim.method in ("fedavg_s", "adaptcl")
        lam = sim.lam if sparse else 0.0
        trainer = env.trainer
        unit_map = env.unit_map
        base_shapes = env.base_shapes
        flat = flatten_unit_space(env.space)
        U = flat.num_units
        mesh = getattr(sim, "mesh", None)
        state_sharding = (
            fleet_sharding(mesh, sim.fleet_axis) if mesh is not None else None
        )

        # robust-aggregation statics (byzantine transform / lossy channel /
        # clip-trim-quarantine).  All None => the chunk program and every host
        # array below are byte-for-byte the pre-feature ones.
        faults_cfg = (
            sim.scenario.faults
            if sim.scenario is not None and sim.scenario.faults is not None
            else None
        )
        byz_cfg = faults_cfg.byzantine if faults_cfg is not None else None
        ch_cfg = faults_cfg.channel if faults_cfg is not None else None
        corrupt_on = ch_cfg is not None and ch_cfg.corrupt > 0.0
        rb_cfg = (
            sim.robust
            if sim.robust is not None and sim.robust.any_active else None
        )
        quar_cfg = rb_cfg.quarantine if rb_cfg is not None else None
        robust_on = (
            byz_cfg is not None or ch_cfg is not None or rb_cfg is not None
        )
        quarantined_commits = 0

        scen = ScenarioEngine(sim.scenario, W) if sim.scenario is not None else None
        if scen is not None:
            plan_all = scen.draw_all(
                sim.rounds,
                shard_sizes=[len(s) for s in env.shards],
                train_len=len(env.task.y_train),
            )
        else:
            plan_all = ScenarioPlan.full(sim.rounds, W)

        shard_x, shard_y = zip(*(env.shard_xy(w) for w in range(W)))
        state = env.fleet.init_state(
            env.base_params, list(shard_x), list(shard_y),
            sharding=state_sharding,
        )
        if sim.resident_momentum:
            env.fleet.init_momentum(state)

        batch = sim.batch_size
        pad_a = max(
            plan_steps(len(env.shards[w]), batch, sim.local_epochs)
            for w in range(W)
        )
        pad_b = max(
            plan_steps(len(env.shards[w]), batch, (1 - sim.beta) * sim.local_epochs)
            for w in range(W)
        )
        K_pad = sim.round_fusion if sim.round_fusion > 0 else (
            sim.prune_interval if adapt else 8
        )
        K_pad = max(1, min(K_pad, sim.rounds))

        global_params = {k: np.asarray(v) for k, v in env.base_params.items()}
        global_dev = {k: jnp.asarray(v) for k, v in global_params.items()}
        sizes_dev = jnp.asarray(np.asarray(state.shard_sizes, np.int32))
        # DGC residual accumulators live on device, carried across chunks like
        # the momentum stacks ({} when DGC is off: an empty pytree)
        dgc_res_dev = (
            {
                k: jnp.zeros((W,) + tuple(s), jnp.float32)
                for k, s in env.base_shapes.items()
            }
            if use_dgc else {}
        )
        if use_dgc and state_sharding is not None:
            dgc_res_dev = jax.device_put(dgc_res_dev, state_sharding)

        indices = [full_index(env.space) for _ in range(W)]
        histories = [WorkerHistory() for _ in range(W)]
        pending_rates = [0.0] * W
        cig_scores = None
        interval_phis: List[List[float]] = [[] for _ in range(W)]
        prune_round_count = 0
        prune_events = []
        fused_chunks = 0

        clock = 0.0
        comm_bytes = 0.0
        acc_time, het_traj, sim_traj, upd_times = [], [], [], []
        scen_rows = []

        # channel-model cache: payload bytes + FLOPs depend on the index only
        # through per-layer retained COUNTS, so the per-(round, worker) phi math
        # collapses to a dict lookup + the exact float ops of _phi_from_shapes —
        # bit-identical values, O(distinct retentions) instead of O(R x W) host
        # shape walks
        _count_cache: Dict[tuple, tuple] = {}

        def _bytes_flops(idx) -> tuple:
            key = tuple(len(idx[name]) for name in flat.names)
            ent = _count_cache.get(key)
            if ent is None:
                shapes = subparam_shapes(idx, unit_map, base_shapes)
                ent = (
                    sum(int(np.prod(s)) * 4 for s in shapes.values()),
                    cnn_flops_from_shapes(shapes, sim.cnn),
                )
                _count_cache[key] = ent
            return ent

        acc_time.append((0.0, _env_accuracy(env, global_params)))
        rt_base = roundtrip_total()

        sig_shapes = tuple(
            sorted((k, tuple(v.shape)) for k, v in state.params.items())
        )
        mesh_sig = (
            (sim.fleet_axis, tuple(mesh.axis_names), mesh.devices.shape,
             tuple(int(d.id) for d in mesh.devices.flat))
            if mesh is not None else None
        )
        rb_sig = (
            ((byz_cfg.mode, float(byz_cfg.scale), float(byz_cfg.noise_std))
             if byz_cfg is not None else None),
            (float(ch_cfg.corrupt_std) if corrupt_on else None,
             ch_cfg is not None),
            ((None if rb_cfg.clip is None else float(rb_cfg.clip),
              float(rb_cfg.trim),
              ((float(quar_cfg.threshold), int(quar_cfg.strikes),
                int(quar_cfg.probation)) if quar_cfg is not None else None))
             if rb_cfg is not None else None),
            # the noise keys' seed is compiled in on the robust path only
            int(sim.seed) if robust_on else None,
        )
        sig = (
            sig_shapes,
            ("fused", K_pad, pad_a, pad_b, tuple(state.xs.shape), batch,
             sim.aggregation, sim.importance, bool(sim.resident_momentum),
             float(sim.dgc_sparsity), mesh_sig, rb_sig),
            float(lam),
        )
        build = lambda: _build_chunk_fn(
            trainer, unit_map, base_shapes, flat, lam,
            by_unit=sim.aggregation == "by_unit",
            importance=sim.importance,
            resident_momentum=bool(sim.resident_momentum),
            has_phase_b=pad_b > 0,
            dgc_sparsity=float(sim.dgc_sparsity),
            mesh=mesh, fleet_axis=sim.fleet_axis,
            robust=rb_cfg, byz=byz_cfg,
            corrupt_std=float(ch_cfg.corrupt_std) if corrupt_on else None,
            channel=ch_cfg is not None,
            noise_seed=int(sim.seed) if robust_on else 0,
            fleet_w=W if mesh is not None else None,
        )
        # quarantine health carry: full-fleet [W] rows, replicated on the mesh
        health_dev = (
            {"strikes": jnp.zeros(W, jnp.int32), "quar": jnp.zeros(W, jnp.int32)}
            if quar_cfg is not None else {}
        )
        if mesh is not None:
            # the chunk returns the global and health carries replicated on the
            # mesh; start them there too, or the second chunk call sees new
            # input shardings and jit compiles the chunk program again
            global_dev, health_dev = jax.device_put(
                (global_dev, health_dev), NamedSharding(mesh, P())
            )

    t = 0
    while t < sim.rounds:
        with span("chunk.plan"):
            # ---- chunk-start churn (host): replaced slots restart fresh ------
            ev0 = plan_all.events[t]
            if ev0.joined.any():
                for w in np.flatnonzero(ev0.joined):
                    w = int(w)
                    indices[w] = full_index(env.space)
                    histories[w] = WorkerHistory()
                    pending_rates[w] = 0.0
                    interval_phis[w] = []
                    env.shards[w] = plan_all.fresh_shards[t][w]
                    env.fleet.update_shard(state, w, *env.shard_xy(w))
                    if sim.resident_momentum:
                        state.momentum = {
                            k: v.at[w].set(0.0) for k, v in state.momentum.items()
                        }
                    if use_dgc:     # fresh slot: no carried residual
                        dgc_res_dev = {
                            k: v.at[w].set(0.0) for k, v in dgc_res_dev.items()
                        }
            # ---- FedDST mask readjustment at the chunk boundary (host).  The
            # chunk-extent cut below guarantees a regrow round is always round
            # t+1 of some chunk, so the shared host step runs here and the chunk
            # simply starts from the readjusted presence rows.  Params need no
            # touch-up (the in-scan broadcast-back re-masks them); momentum rows
            # must drop newly-removed units explicitly when resident.
            if _regrow_round(sim, t + 1):
                regrown = _regrow_step(sim, env, global_params, indices, t + 1)
                for w, idx_w in regrown:
                    prune_events.append((
                        t + 1, int(w),
                        {k: tuple(map(int, v)) for k, v in idx_w.items()},
                    ))
                if regrown and sim.resident_momentum:
                    pres_now = jnp.asarray(np.stack([
                        presence_from_index(indices[w], flat) for w in range(W)
                    ]))
                    m_now = masks_from_presence(
                        pres_now, flat, unit_map, base_shapes
                    )
                    state.momentum = {
                        k: v * m_now[k] for k, v in state.momentum.items()
                    }
            # ---- chunk extent: learning events, churn, regrow and capability
            # drift rounds cut.  A drift-change round must be the LAST round of
            # its chunk (the cut fires when the PREVIOUS round drifted), so the
            # drift-triggered re-learning runs at the chunk boundary exactly
            # where the lazy loop runs it.  Outage/skip rounds do NOT cut —
            # they ride in-scan as dead rounds (real=False).
            n = min(K_pad, sim.rounds - t)
            if adapt:
                n = min(n, sim.prune_interval - (t % sim.prune_interval))
            for j in range(1, n):
                if (plan_all.events[t + j].joined.any()
                        or _regrow_round(sim, t + j + 1)
                        or (scen is not None and scen.drift_changed(t + j))):
                    n = j
                    break
            rounds_this = list(range(t + 1, t + n + 1))

            # ---- host pre-compute: plans / budgets / jitter, in the lazy
            # loop's exact env.rng order (plans then jitter, per round) --------
            plans_a = np.zeros((K_pad, W, pad_a, batch), np.int64)
            valid_a = np.zeros((K_pad, W, pad_a), np.float32)
            plans_b = np.zeros((K_pad, W, max(pad_b, 1), batch), np.int64)
            valid_b = np.zeros((K_pad, W, max(pad_b, 1)), np.float32)
            budgets = np.zeros((K_pad, W), np.int32)
            prune_any = np.zeros((K_pad,), bool)
            real = np.zeros((K_pad,), bool)
            weights = np.zeros((K_pad, W), np.float32)
            submit_m = np.zeros((K_pad, W), np.float32)
            mult_m = np.zeros((K_pad, W), np.float32)
            byz_m = np.zeros((K_pad, W), bool)
            cor_m = np.zeros((K_pad, W), bool)
            rnd_arr = np.zeros((K_pad,), np.int32)
            jitters = np.ones((K_pad, W))
            recov = np.zeros((K_pad, W), np.float32)
            drmat = np.ones((K_pad, W))
            steps_a = np.zeros((K_pad, W), np.int64)
            steps_b = np.zeros((K_pad, W), np.int64)
            active_list: List[List[int]] = []
            prune_now_rounds: List[np.ndarray] = []

            for j, rnd in enumerate(rounds_this):
                ev = plan_all.events[rnd - 1]
                active_ws = [int(w) for w in np.flatnonzero(ev.active)]
                active_list.append(active_ws)
                if scen is not None:
                    scen_rows.append((
                        rnd, len(active_ws),
                        int(ev.dropped.sum()), int(ev.joined.sum()),
                    ))
                # crash recovery rides the scan: a 1.0 in recov[j, w] zeroes the
                # worker's momentum/DGC-residual rows at the top of round j's
                # scan step — the in-scan mirror of the lazy loop's host-side
                # zero_momentum_rows/residual reset.  Applies on skip rounds too
                # (the lazy loop does its recovery bookkeeping before skipping).
                if ev.recovered is not None:
                    recov[j] = ev.recovered.astype(np.float32)
                if (scen is not None and scen.cfg.faults is not None
                        and scen.cfg.faults.drift is not None):
                    drmat[j] = scen.drift_mults(rnd)
                if ev.skip:
                    # degraded-floor round: rides the scan as a dead round
                    # (real=False, all-zero valid/submitters → the global carry
                    # passes through untouched).  The lazy skip branch draws no
                    # plans/jitter and resets no pending rates, so neither does
                    # this one: zero env.rng draws either way.
                    prune_now_rounds.append(np.zeros(W, bool))
                    continue
                pa: List[Optional[np.ndarray]] = [None] * W
                pb: List[Optional[np.ndarray]] = [None] * W
                pn = np.zeros(W, bool)
                for w in active_ws:
                    rate = pending_rates[w] if adapt else 0.0
                    if adapt and rate > 0.0:
                        e1 = sim.beta * sim.local_epochs
                        e2 = (1 - sim.beta) * sim.local_epochs
                        pn[w] = True
                    else:
                        e1, e2 = sim.local_epochs, 0.0
                    nsh = len(env.shards[w])
                    pa[w] = make_batch_plan(nsh, batch, e1, env.rng)
                    pb[w] = make_batch_plan(nsh, batch, e2, env.rng)
                    steps_a[j, w] = pa[w].shape[0]
                    steps_b[j, w] = pb[w].shape[0]
                prune_now_rounds.append(pn)
                for w in active_ws:
                    if pn[w]:
                        budgets[j, w] = prune_budget_units(
                            indices[w], pending_rates[w], env.space
                        )
                prune_any[j] = bool(pn.any())
                sa = stack_batch_plans(pa, num_rows=W, num_steps=pad_a)
                if sa is not None:
                    plans_a[j], valid_a[j] = sa
                if pad_b > 0:
                    sb = stack_batch_plans(pb, num_rows=W, num_steps=pad_b)
                    if sb is not None:
                        plans_b[j], valid_b[j] = sb
                submit_m[j] = ev.submitters.astype(np.float32)
                # commit multiplicity: submitters x delivery x duplication.  With
                # no channel this IS the submitter indicator, so the f64 division
                # below matches the pre-feature weights bit-for-bit.
                mult_j = ev.submitters.astype(np.float64)
                if ev.delivered is not None:
                    mult_j = mult_j * ev.delivered * (1.0 + ev.dup)
                mult_m[j] = mult_j.astype(np.float32)
                if sim.aggregation != "by_unit":
                    ms = mult_j.sum()
                    if ms > 0:
                        weights[j] = (mult_j / ms).astype(np.float32)
                if ev.byz is not None:
                    byz_m[j] = ev.byz & ev.submitters
                if corrupt_on and ev.corrupt is not None:
                    cor_m[j] = ev.corrupt & ev.delivered & ev.submitters
                rnd_arr[j] = rnd
                real[j] = True
                if sim.time_jitter > 0:
                    for w in active_ws:
                        jitters[j, w] = float(
                            np.exp(env.rng.normal(0, sim.time_jitter))
                        )
                for w in active_ws:      # submission resets the pending rate
                    pending_rates[w] = 0.0

            orders_np = None
            if sim.importance in STATIC_METHODS:
                orders_np = _static_orders(sim, env, flat, cig_scores,
                                           prune_round_count)
            orders_dev = jnp.asarray(
                orders_np if orders_np is not None
                else np.zeros((W, U), np.int32)
            )
            presence_dev = jnp.asarray(
                np.stack([presence_from_index(indices[w], flat) for w in range(W)])
            )
            per_round = {
                "plan_a": jnp.asarray(plans_a.astype(np.int32)),
                "valid_a": jnp.asarray(valid_a),
                "budgets": jnp.asarray(budgets),
                "prune_any": jnp.asarray(prune_any),
                "real": jnp.asarray(real),
                "weights": jnp.asarray(weights),
                "submitters": jnp.asarray(submit_m),
                "recov": jnp.asarray(recov),
            }
            if pad_b > 0:
                per_round["plan_b"] = jnp.asarray(plans_b.astype(np.int32))
                per_round["valid_b"] = jnp.asarray(valid_b)
            if robust_on:
                per_round["mult"] = jnp.asarray(mult_m)
                per_round["rnd"] = jnp.asarray(rnd_arr)
                if byz_cfg is not None:
                    per_round["byz"] = jnp.asarray(byz_m)
                if corrupt_on:
                    per_round["corrupt"] = jnp.asarray(cor_m)
            momentum_arg = state.momentum if sim.resident_momentum else {}

        # ---- ONE device dispatch for the whole chunk ---------------------
        with span("chunk.dispatch"):
            (state.params, mom_out, _, global_dev, dgc_res_dev, health_dev,
             pres_seq, glob_seq, kept_seq, total_seq, quar_seq) = (
                trainer._call_cached(
                    sig, build,
                    state.params, momentum_arg, presence_dev, global_dev,
                    dgc_res_dev, health_dev, state.xs, state.ys, sizes_dev,
                    per_round, orders_dev,
                )
            )
        if sim.resident_momentum:
            state.momentum = mom_out
        fused_chunks += 1
        env.fleet.batched_calls += 1
        env.fleet.buckets_used.add(W)

        with span("chunk.readback"):
            pres_seq_np = np.asarray(pres_seq)                     # [K, W, U]
            glob_seq_np = {k: np.asarray(v) for k, v in glob_seq.items()}
            if use_dgc:                                            # [K, W] ints
                kept_np = np.asarray(kept_seq)
                total_np = np.asarray(total_seq)
            if quar_cfg is not None:                               # [K, W] 0/1
                quar_np = np.asarray(quar_seq)

        # ---- post-chunk host accounting (payloads, clock, ledger); the
        # rounds' evaluations follow it, in round order ------------------
        evals = []
        with span("chunk.account"):
            for j, rnd in enumerate(rounds_this):
                ev = plan_all.events[rnd - 1]
                active_ws = active_list[j]
                pn = prune_now_rounds[j]
                if ev.skip:
                    # degraded floor: the global is untouched (dead scan round),
                    # the virtual clock waits out the straggler deadline, no
                    # update times land.  Evals still fire — glob_seq[j] is the
                    # pass-through carry, identical to the lazy skip branch's
                    # unchanged global_params.
                    clock += _skip_round_time(env, scen, indices, rnd)
                    upd_times.append([float("nan")] * W)
                    if rnd % sim.eval_every == 0:
                        evals.append((clock, j))
                    continue
                for w in active_ws:     # ledger phase A at the pre-prune index
                    env.account_train(indices[w], int(steps_a[j, w]))
                for w in active_ws:
                    if pn[w]:
                        indices[w] = index_from_presence(pres_seq_np[j, w], flat)
                        prune_events.append((
                            rnd, int(w),
                            {k: tuple(map(int, v)) for k, v in indices[w].items()},
                        ))
                        if pad_b > 0:   # ledger phase B at the pruned index
                            env.account_train(indices[w], int(steps_b[j, w]))
                if quar_cfg is not None:
                    # commits excluded by the server this round: quarantined row
                    # AND a payload actually arrived (mult > 0)
                    quarantined_commits += int(
                        ((quar_np[j] > 0.5) & (mult_m[j] > 0)).sum()
                    )
                phis = np.full(W, np.nan)
                for w in active_ws:
                    bytes_w, flops_w = _bytes_flops(indices[w])
                    # the host path's exact DGC payload factor, rebuilt from the
                    # realized on-device kept/total integers (submitters only —
                    # non-submitters pay full price, matching _run_sync)
                    pf = 1.0
                    if use_dgc and ev.submitters[w]:
                        pf = 1.25 * float(kept_np[j, w]) / max(
                            float(total_np[j, w]), 1.0
                        )
                    # jitter x drift multiplied HERE (one float product) so the
                    # value is bit-identical to the lazy path's
                    # phi_from_cost(..., jmult * time_mult); channel retries
                    # stretch the drift factor FIRST (d*r), then jitter — the
                    # masked loop associates its floats the same way.
                    retry_mult = 1.0
                    if (ch_cfg is not None and ev.retries is not None
                            and ev.submitters[w]):
                        retry_mult = (
                            1.0 + ch_cfg.retry_backoff * float(ev.retries[w])
                        )
                    phi_w = env.phi_from_cost(
                        w, bytes_w, flops_w, pf,
                        jitters[j, w] * (drmat[j, w] * retry_mult),
                    )
                    phis[w] = phi_w
                    interval_phis[w].append(phi_w)
                    if ev.submitters[w]:
                        extra = 0.0
                        if ch_cfg is not None and ev.retries is not None:
                            extra = (
                                float(ev.retries[w])
                                + float(ev.dup[w] & ev.delivered[w])
                            ) * pf * bytes_w
                        comm_bytes += 2.0 * pf * bytes_w + extra
                sub_phis = phis[ev.submitters]
                round_time = float(sub_phis.max())
                if ev.dropped.any() and scen is not None:
                    round_time *= scen.cfg.timeout_factor
                clock += round_time
                upd_times.append(list(phis))
                het_traj.append((rnd, heterogeneity_from_times(sub_phis)))
                if W > 3:
                    sim_traj.append((rnd, similarity(indices[1], indices[3])))
                if rnd % sim.eval_every == 0:
                    evals.append((clock, j))
        for clock_j, j in evals:
            g_j = {k: v[j] for k, v in glob_seq_np.items()}
            acc_time.append((clock_j, _env_accuracy(env, g_j)))
        global_params = {k: np.array(v[n - 1]) for k, v in glob_seq_np.items()}
        t += n

        # ---- learning event at the chunk boundary (host Newton math).
        # Drift-change rounds always cut their chunk (see the extent rule),
        # so a drift-triggered re-learning fires HERE, exactly one round
        # after the capability changed — same timing as the lazy loop.
        drift_now = scen is not None and scen.drift_changed(t)
        if adapt and (t % sim.prune_interval == 0 or drift_now):
            with span("alg2"):
                prune_round_count += 1
                if cig_scores is None and sim.importance == "cig_bnscalor":
                    cig_scores = METHODS["cig_bnscalor"](ImportanceContext(
                        unit_counts=env.space.unit_counts,
                        scales=extract_bn_scales(global_params, sim.cnn),
                    ))
                if drift_now:
                    histories[sim.scenario.faults.drift.worker].invalidate()
                mults = scen.drift_mults(t) if scen is not None else np.ones(W)
                gammas_now = [retention(indices[w], env.space) for w in range(W)]
                phis_now = [
                    float(np.mean(interval_phis[w])) if interval_phis[w]
                    else env.phi_from_index(
                        w, indices[w], jitter=False, time_mult=float(mults[w])
                    )
                    for w in range(W)
                ]
                for w in range(W):
                    histories[w].record(gammas_now[w], phis_now[w])
                if sim.fixed_pruned_rates is not None:
                    k = prune_round_count - 1
                    rates = (
                        sim.fixed_pruned_rates[k]
                        if k < len(sim.fixed_pruned_rates)
                        else [0.0] * W
                    )
                else:
                    rates = learn_pruned_rates(
                        histories, gammas_now, phis_now, sim.rate_cfg
                    )
                pending_rates = list(rates)
                interval_phis = [[] for _ in range(W)]

    host_roundtrips = roundtrip_total() - rt_base
    final_costs = [env.cost_for_index(indices[w]) for w in range(W)]
    return _finalize(
        sim, env, acc_time, het_traj, sim_traj, upd_times,
        [retention(indices[w], env.space) for w in range(W)],
        [extract_subparams(global_params, indices[w], unit_map)
         for w in range(W)],
        comm_bytes, clock,
        global_params=global_params, host_roundtrips=host_roundtrips,
        scenario_rounds=scen_rows,
        flops_per_image_final=float(np.mean([c[0] for c in final_costs])),
        blocks_per_image_final=float(np.mean([c[2] for c in final_costs])),
        prune_events=prune_events, fused_chunks=fused_chunks,
        fault_ledger={
            **fault_ledger(plan_all.events),
            "quarantined_commits": quarantined_commits,
        },
        stack_devices=len(next(iter(state.params.values())).sharding.device_set),
    )


# ---------------------------------------------------------------------------
# fused ASYNC engine: the discrete-event loop itself as lax.scan chunks
# ---------------------------------------------------------------------------

def split_time_keys(finishes: np.ndarray):
    """Split float64 finish times into two float32 sort keys.

    ``hi`` is the f32 rounding of the time, ``lo`` the f64 residual cast to
    f32; because f32 rounding is monotone, ``(hi, lo)`` lexicographic order
    equals f64 order except for residual-level collisions (~2^-48 apart),
    which the fused driver's runtime order check turns into a hard error
    instead of a silent reorder."""
    hi = finishes.astype(np.float32)
    lo = (finishes - hi.astype(np.float64)).astype(np.float32)
    return hi, lo


def async_pop_perm(fin_hi, fin_lo, rows):
    """Device pending-queue pop order: the sorted-finish-times replacement
    for the host ``heapq`` pop.  A stable ``lexsort`` over (primary) the
    split finish keys then (tertiary) the worker index reproduces the host
    heap's ``(time, worker_index)`` tuple ordering exactly — ties in finish
    time pop in ascending worker order.  Padding slots carry ``hi = +inf``
    so they sort to the tail."""
    return jnp.lexsort((rows, fin_lo, fin_hi))


def _build_async_chunk_fn(trainer, unit_map, base_shapes, lam, *, method, W,
                          BP, EB, cohort_size, fedasync_a, lr,
                          dcasgd_lambda, dcasgd_m,
                          clip_norm=None, quarantine=None):
    """Build the jitted async chunk program: ``lax.scan`` over KB window
    batches, each popping its events from a device queue, training the
    batch's workers as one vmapped sub-stack, then walking the commits
    through an inner scan of ``async_commit_jnp`` merges.

    Carry: (fetched ``[W, ...]`` snapshots, global params, server version,
    per-slot ``fetched_ver``, dcasgd backup/accumulator).  Per-batch inputs
    arrive in heap PUSH order; outputs are the popped worker order and
    staleness integers (the host verifies both against the plan) plus the
    post-commit globals captured at eval events."""
    train_one = trainer.make_resident_train(unit_map, lam)
    vm_train = jax.vmap(
        lambda p, x, y, plan, valid, mask, gl:
            train_one(p, x, y, plan, valid, mask, gl)
    )
    gl_base = group_size_sqrt_from_shapes(base_shapes, unit_map)

    def chunk(fetched, g, version, fetched_ver, backup, dc_m, health, xs, ys,
              per_batch):
        # async workers never prune: masks are all-ones, group-lasso factors
        # are the base-shape constants
        masks = {
            k: jnp.ones((BP,) + tuple(base_shapes[k]), jnp.float32)
            for k in fetched
        }
        gl = {
            lname: jnp.full((BP,), s, jnp.float32)
            for lname, s in gl_base.items()
        }

        def commit_body(c, e):
            g, version, fetched_ver, fetched, backup, dc_m, health, eval_buf = c
            w, v_ok, drop, t_row, f_row, ref_row, ev_flag, ev_slot = e
            s = version - fetched_ver[w]
            live = v_ok * (1.0 - drop)     # merged = real AND not timed out
            g2, backup2, dc_m2 = async_commit_jnp(
                method, g, t_row, f_row, s, w, backup, dc_m,
                cohort_size=cohort_size, fedasync_a=fedasync_a, lr=lr,
                dcasgd_lambda=dcasgd_lambda, dcasgd_m=dcasgd_m,
                clip_norm=clip_norm,
            )
            keep = live > 0
            if quarantine is not None:
                # per-commit MAD-outlier health: only LIVE commits touch the
                # tracker (dropped/padding slots must not move the median
                # population), and a rejected commit keeps the global but
                # still bumps the version below — the pre-planned version
                # trajectory is fixed.
                hk = live > 0
                delta = {k: t_row[k] - f_row[k] for k in t_row}
                norm = delta_norms_jnp(
                    {k: d[None] for k, d in delta.items()}
                )[0]
                reject, st2, qu2, nm2, sn2 = async_health_step_jnp(
                    norm, w, health["strikes"], health["quar"],
                    health["norms"], health["seen"],
                    threshold=quarantine.threshold,
                    strikes_needed=quarantine.strikes,
                    probation=quarantine.probation,
                )
                health = {
                    "strikes": jnp.where(hk, st2, health["strikes"]),
                    "quar": jnp.where(hk, qu2, health["quar"]),
                    "norms": jnp.where(hk, nm2, health["norms"]),
                    "seen": jnp.where(hk, sn2, health["seen"]),
                    "rejected": health["rejected"]
                    + (hk & reject).astype(jnp.int32),
                }
                keep = hk & ~reject
            g = {k: jnp.where(keep, g2[k], g[k]) for k in g}
            backup = {k: jnp.where(keep, backup2[k], backup[k]) for k in backup}
            dc_m = {k: jnp.where(keep, dc_m2[k], dc_m[k]) for k in dc_m}
            version = version + live.astype(jnp.int32)
            # refetch AFTER the bump: dropped commits refetch the unchanged
            # global; padding slots (v_ok = 0) touch nothing
            ref_eff = ref_row * v_ok
            fetched = refetch_rows_jnp(fetched, ref_eff, g)
            fetched_ver = jnp.where(ref_eff > 0, version, fetched_ver)
            wr = (ev_flag * v_ok) > 0
            eval_buf = {
                k: eval_buf[k].at[ev_slot].set(
                    jnp.where(wr, g[k], eval_buf[k][ev_slot])
                )
                for k in eval_buf
            }
            return (g, version, fetched_ver, fetched, backup, dc_m, health,
                    eval_buf), (w, s)

        def body(carry, inp):
            fetched, g, version, fetched_ver, backup, dc_m, health = carry
            # device queue pop: push-ordered events -> commit order
            perm = async_pop_perm(inp["fin_hi"], inp["fin_lo"], inp["rows"])
            rows = jnp.take(inp["rows"], perm)
            valid = jnp.take(inp["valid"], perm)
            dropped = jnp.take(inp["dropped"], perm)
            plans = jnp.take(inp["plans"], perm, axis=0)
            pvalid = jnp.take(inp["pvalid"], perm, axis=0)
            refetch = jnp.take(inp["refetch"], perm, axis=0)
            eval_flag = jnp.take(inp["eval_flag"], perm)
            eval_slot = jnp.take(inp["eval_slot"], perm)
            # masked gather-in of each popped worker's fetched snapshot +
            # shard, then ONE vmapped bucket-sized training for the batch
            # (within a batch every worker is distinct and its input was
            # fixed at its last refetch, so batched training is exact)
            p0 = {k: jnp.take(v, rows, axis=0) for k, v in fetched.items()}
            xb = jnp.take(xs, rows, axis=0)
            yb = jnp.take(ys, rows, axis=0)
            trained, _, _ = vm_train(p0, xb, yb, plans, pvalid, masks, gl)
            eval_buf = {
                k: jnp.zeros((EB,) + tuple(base_shapes[k]), jnp.float32)
                for k in g
            }
            (g, version, fetched_ver, fetched, backup, dc_m, health,
             eval_buf), (
                order, stale
            ) = jax.lax.scan(
                commit_body,
                (g, version, fetched_ver, fetched, backup, dc_m, health,
                 eval_buf),
                (rows, valid, dropped, trained, p0, refetch, eval_flag,
                 eval_slot),
            )
            return (fetched, g, version, fetched_ver, backup, dc_m,
                    health), (order, stale, eval_buf)

        carry0 = (fetched, g, version, fetched_ver, backup, dc_m, health)
        (fetched, g, version, fetched_ver, backup, dc_m, health), (
            order_seq, stale_seq, eval_seq
        ) = jax.lax.scan(body, carry0, per_batch)
        return (fetched, g, version, fetched_ver, backup, dc_m, health,
                order_seq, stale_seq, eval_seq)

    return jax.jit(chunk)


def run_async_fused(sim, env, scen, participants, plan):
    """Async simulation with the fused event-queue engine (see module doc).

    Replays the SAME pre-simulated ``AsyncEventPlan`` as the resident/
    per-worker engines (``simulation._run_async`` builds it and routes
    here), so commit order, staleness weights, dropout outcomes and virtual
    clocks are identical by construction; chunks of ``round_fusion`` window
    batches run as one device program each."""
    from .simulation import _env_accuracy, _finalize   # lazy: no import cycle

    validate_fused_config(sim)
    W = sim.num_workers
    method = sim.method
    lam = sim.lam
    trainer = env.trainer
    unit_map = env.unit_map
    base_shapes = env.base_shapes
    n_part = len(participants)
    idx = full_index(env.space)
    # robust layer (async half): norm clip + quarantine; trim was rejected
    # by name in _run_async before routing here
    rb_cfg = (
        sim.robust if sim.robust is not None and sim.robust.any_active
        else None
    )
    clip_norm = rb_cfg.clip if rb_cfg is not None else None
    quar_cfg = rb_cfg.quarantine if rb_cfg is not None else None

    global_params = {k: np.asarray(v) for k, v in env.base_params.items()}
    acc_time = [(0.0, _env_accuracy(env, global_params))]
    rt_base = roundtrip_total()
    # async commits always move base-shape payloads (workers never prune)
    commit_bytes = 2.0 * sum(
        int(np.prod(s)) * 4 for s in base_shapes.values()
    )
    comm_bytes = 0.0
    fused_chunks = 0
    final_cost = env.cost_for_index(idx)

    E = plan.num_events
    if E == 0:
        return _finalize(sim, env, acc_time, [], [], [], [1.0] * W,
                         [dict(global_params) for _ in range(W)], 0.0,
                         0.0, global_params=dict(global_params),
                         host_roundtrips=roundtrip_total() - rt_base,
                         scenario_rounds=(
                             [(0, n_part, 0, 0)] if scen is not None else []
                         ),
                         flops_per_image_final=final_cost[0],
                         blocks_per_image_final=final_cost[2],
                         fused_chunks=0,
                         fault_ledger={
                             **(plan.fault_ledger or {}),
                             "quarantined_commits": 0,
                         })

    shard_x, shard_y = zip(*(env.shard_xy(w) for w in range(W)))
    state = env.fleet.init_state(env.base_params, list(shard_x), list(shard_y))

    batch = sim.batch_size
    pad_steps = max(
        plan_steps(len(env.shards[w]), batch, sim.local_epochs)
        for w in participants
    )
    S_eff = max(pad_steps, 1)      # static step dim even for no-step plans
    n_batches = len(plan.batch_starts) - 1
    BP = int(np.diff(plan.batch_starts).max())
    EB = max(
        max(
            int(plan.evals[int(plan.batch_starts[b]):
                           int(plan.batch_starts[b + 1])].sum())
            for b in range(n_batches)
        ),
        1,
    )
    KB = sim.round_fusion if sim.round_fusion > 0 else 8
    KB = max(1, min(KB, n_batches))

    # eval slots: exclusive cumsum of eval flags within each batch
    slot_of = np.zeros(E, np.int64)
    for b in range(n_batches):
        s0, e0 = int(plan.batch_starts[b]), int(plan.batch_starts[b + 1])
        ev = plan.evals[s0:e0].astype(np.int64)
        slot_of[s0:e0] = np.cumsum(ev) - ev
    fin_hi_all, fin_lo_all = split_time_keys(plan.finishes)

    g_dev = {k: jnp.asarray(v, jnp.float32) for k, v in global_params.items()}
    fetched_dev = state.params     # [W, ...] broadcast of the base params
    version_dev = jnp.asarray(0, jnp.int32)
    fetched_ver_dev = jnp.zeros((W,), jnp.int32)
    if method == "dcasgd_s":
        backup_dev = dict(fetched_dev)   # per-slot w_bak starts at the global
        dc_m_dev = {k: jnp.zeros_like(v) for k, v in g_dev.items()}
    else:
        backup_dev, dc_m_dev = {}, {}
    health_dev = (
        {
            "strikes": jnp.zeros(W, jnp.int32),
            "quar": jnp.zeros(W, jnp.int32),
            "norms": jnp.zeros(W, jnp.float32),
            "seen": jnp.zeros(W, bool),
            "rejected": jnp.asarray(0, jnp.int32),
        }
        if quar_cfg is not None else {}
    )

    sig_shapes = tuple(
        sorted((k, tuple(v.shape)) for k, v in state.params.items())
    )
    rb_sig = (
        None if clip_norm is None else float(clip_norm),
        ((float(quar_cfg.threshold), int(quar_cfg.strikes),
          int(quar_cfg.probation)) if quar_cfg is not None else None),
    )
    sig = (
        sig_shapes,
        ("fused_async", method, KB, BP, S_eff, EB, tuple(state.xs.shape),
         batch, n_part, float(sim.fedasync_a), float(sim.lr),
         float(sim.dcasgd_lambda), float(sim.dcasgd_m), rb_sig),
        float(lam),
    )
    build = lambda: _build_async_chunk_fn(
        trainer, unit_map, base_shapes, lam, method=method, W=W, BP=BP,
        EB=EB, cohort_size=n_part, fedasync_a=float(sim.fedasync_a),
        lr=float(sim.lr), dcasgd_lambda=float(sim.dcasgd_lambda),
        dcasgd_m=float(sim.dcasgd_m),
        clip_norm=None if clip_norm is None else float(clip_norm),
        quarantine=quar_cfg,
    )

    b = 0
    while b < n_batches:
        nc = min(KB, n_batches - b)
        rows_a = np.zeros((KB, BP), np.int32)
        valid_a = np.zeros((KB, BP), np.float32)
        drop_a = np.zeros((KB, BP), np.float32)
        # padding slots: +inf finish keys sort them past every real event
        # (built explicitly — inf-residual arithmetic would NaN the keys)
        hi_a = np.full((KB, BP), np.inf, np.float32)
        lo_a = np.zeros((KB, BP), np.float32)
        plans_a = np.zeros((KB, BP, S_eff, batch), np.int32)
        pvalid_a = np.zeros((KB, BP, S_eff), np.float32)
        ref_a = np.zeros((KB, BP, W), np.float32)
        evf_a = np.zeros((KB, BP), np.float32)
        evs_a = np.zeros((KB, BP), np.int32)
        for j in range(nc):
            s0 = int(plan.batch_starts[b + j])
            e0 = int(plan.batch_starts[b + j + 1])
            L = e0 - s0
            # feed the device queue in heap PUSH order — the in-scan pop
            # must genuinely re-derive the commit order
            feed = s0 + np.argsort(plan.push_seq[s0:e0], kind="stable")
            rows_a[j, :L] = plan.workers[feed]
            valid_a[j, :L] = 1.0
            drop_a[j, :L] = plan.dropped[feed]
            hi_a[j, :L] = fin_hi_all[feed]
            lo_a[j, :L] = fin_lo_all[feed]
            ref_a[j, :L] = plan.refetch[feed]
            evf_a[j, :L] = plan.evals[feed]
            evs_a[j, :L] = slot_of[feed]
            for r, i in enumerate(feed):
                p = plan.plans[i]
                if p.shape[0]:
                    plans_a[j, r, :p.shape[0]] = p
                    pvalid_a[j, r, :p.shape[0]] = 1.0
        per_batch = {
            "rows": jnp.asarray(rows_a),
            "valid": jnp.asarray(valid_a),
            "dropped": jnp.asarray(drop_a),
            "fin_hi": jnp.asarray(hi_a),
            "fin_lo": jnp.asarray(lo_a),
            "plans": jnp.asarray(plans_a),
            "pvalid": jnp.asarray(pvalid_a),
            "refetch": jnp.asarray(ref_a),
            "eval_flag": jnp.asarray(evf_a),
            "eval_slot": jnp.asarray(evs_a),
        }

        # ---- ONE device dispatch for the whole chunk ---------------------
        (fetched_dev, g_dev, version_dev, fetched_ver_dev, backup_dev,
         dc_m_dev, health_dev, order_seq, stale_seq, eval_seq) = (
            trainer._call_cached(
                sig, build, fetched_dev, g_dev, version_dev, fetched_ver_dev,
                backup_dev, dc_m_dev, health_dev, state.xs, state.ys,
                per_batch,
            )
        )
        fused_chunks += 1
        env.fleet.batched_calls += 1
        env.fleet.buckets_used.add(BP)

        order_np = np.asarray(order_seq)
        stale_np = np.asarray(stale_seq)
        eval_np = {k: np.asarray(v) for k, v in eval_seq.items()}
        for j in range(nc):
            s0 = int(plan.batch_starts[b + j])
            e0 = int(plan.batch_starts[b + j + 1])
            L = e0 - s0
            # the device pop must reproduce the host heap replay exactly —
            # commit order (ties included) AND the staleness integers
            if not (
                np.array_equal(order_np[j, :L], plan.workers[s0:e0])
                and np.array_equal(stale_np[j, :L], plan.staleness[s0:e0])
            ):
                raise RuntimeError(
                    "device event queue diverged from host heap replay"
                )
            for i in range(s0, e0):
                env.account_train(idx, plan.plans[i].shape[0])
                if not plan.dropped[i]:
                    comm_bytes += commit_bytes
                if plan.evals[i]:
                    g_i = {k: eval_np[k][j, slot_of[i]] for k in eval_np}
                    acc_time.append(
                        (float(plan.clocks[i]), _env_accuracy(env, g_i))
                    )
        b += nc

    global_params = {k: np.asarray(v) for k, v in g_dev.items()}
    clock = float(plan.clocks[-1])
    host_roundtrips = roundtrip_total() - rt_base
    scen_rows = [(0, n_part, 0, 0)] if scen is not None else []
    rejected = (
        int(np.asarray(health_dev["rejected"]))
        if quar_cfg is not None else 0
    )
    return _finalize(sim, env, acc_time, [], [], [], [1.0] * W,
                     [dict(global_params) for _ in range(W)], comm_bytes,
                     clock, global_params=dict(global_params),
                     host_roundtrips=host_roundtrips,
                     scenario_rounds=scen_rows,
                     flops_per_image_final=final_cost[0],
                     blocks_per_image_final=final_cost[2],
                     fused_chunks=fused_chunks,
                     fault_ledger={
                         **(plan.fault_ledger or {}),
                         "quarantined_commits": rejected,
                     })
