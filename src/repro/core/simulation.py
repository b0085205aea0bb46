"""Multi-worker collaborative-learning simulator (AdaptCL §IV).

Faithful-reproduction engine: W workers with heterogeneous bandwidths (Eq. 6/7
channel model), a virtual clock, and six frameworks:

  * ``adaptcl``    — Algorithm 1 (+ Algorithm 2 pruned-rate learning)
  * ``fedavg``     — McMahan et al. BSP
  * ``fedavg_s``   — + group-lasso sparse training (the paper's main baseline)
  * ``fedasync_s`` — Xie et al. async with polynomial staleness weighting
  * ``ssp_s``      — stale-synchronous parallel (threshold s)
  * ``dcasgd_s``   — DC-ASGD-a (delay-compensated async gradients)

All methods share the same bandwidth assignment, data partition, and model
init, as in the paper.  Update times are simulated through the channel model
(training-time sensitivity to pruning is configurable, Appendix E); virtual
time is what produces the paper's Time columns.

Local training is dispatched through the **fleet engine** (``core.fleet``),
selected by ``SimConfig.engine``:

  * ``"sequential"`` — one scan-train call per worker (reference engine);
  * ``"bucketed"``   — workers sharing a parameter-shape signature are
    stacked and trained in one jitted ``vmap`` call;
  * ``"masked"``     — the **resident** engine: stacked ``[W, ...]``
    base-shape param/mask/momentum arrays live on device across rounds
    (``core.fleet.FleetState``), sub-model identity is carried only by the
    0/1 mask stack, and the synchronous round loop performs ZERO
    ``extract_subparams``/``embed_params`` host round-trips — broadcast-back
    is a masked scatter, training is one vmapped program over the whole
    stack, and aggregation consumes the stacks directly
    (``aggregation.aggregate_by_worker_stacked``).  Extraction happens only
    at the submission/reporting boundary (``SimResult``, data-dependent
    importance scores).  Host cost per round is therefore ~flat in W, which
    is what makes hundreds-of-worker fleets simulable.

Minibatch plans are pre-drawn per worker in a fixed order, so all three
engines consume identical batch sequences and produce numerically equivalent
trained models (``tests/test_fleet_equivalence.py``).

**Scenarios** (``SimConfig.scenario``, ``core.scenario``): per-round client
sampling (fraction C), straggler dropout (timeout semantics), and churn
(slot replacement with fresh shards) apply to the synchronous methods as a
per-round participation mask over the fixed worker slots — under the
resident engine, device shapes never change, so flaky fleets keep the
one-compile guarantee.

The async schedulers' discrete-event timeline is INDEPENDENT of trained
parameter values (async workers never prune, so channel times depend only on
bandwidths + jitter, and SSP blocking only on commit counts).  The entire
run is therefore pre-simulated on host by ``_plan_async_events`` into a
``scenario.AsyncEventPlan`` — commit order (including ``(time, worker)``
finish-tie breaking), staleness integers, dropout outcomes, refetch sets,
window batches and virtual clocks — and every engine replays that ONE plan:

  * the per-worker and resident (``masked``) engines batch event commits
    that land within one virtual window (``SimConfig.async_window``, default
    0 = fully serial) into a single fleet call.  Resident: each window batch
    scatters the committing workers' refetched globals into their
    ``[W, ...]`` rows (masked scatter in), trains the batch as one
    bucket-sized sub-stack program, pulls the trained rows to host in ONE
    copy (stacked aggregate out), and applies the per-commit staleness
    merges (``aggregation.AsyncServer``) in finish order — no
    ``extract_subparams``/``embed_params`` anywhere, so
    ``SimResult.host_roundtrips == 0`` for resident async runs too;
  * the ``fused`` engine (``core.fused.run_async_fused``) moves the event
    loop itself onto the device: the pending-commit queue pop is a device
    ``lexsort`` over sorted finish-time keys, worker clocks / staleness
    counters / the fetched-snapshot stacks are device arrays, and whole
    CHUNKS of window batches — refetch scatter, vmapped training, in-scan
    ``AsyncServer``-equivalent merges — run as one ``lax.scan`` program, so
    ``host_dispatches`` is O(events / round_fusion) instead of O(events).

Async methods honour scenario *client sampling* (a static C-fraction of the
slot pool joins the event loop, ``ScenarioEngine.static_participants``) and
*dropout* (each commit independently times out at the server with
probability ``dropout``: it still trains, counts and refetches, but its
update is discarded — no merge, no version bump, no communicated bytes);
churn and scripted schedules stay sync-only.  Device compute is sized to
the participants.

``SimResult`` reports ``recompiles`` (jit shape-signatures compiled),
``batched_calls`` (device programs launched by the batched engines),
``walltime_s`` (host wall-clock), ``host_roundtrips`` (extract/embed calls
plus per-worker async merge copies inside the loop — 0 for the resident
engine), and ``bucket_sizes`` (the sub-stack row buckets launched, which
bound the recompile count) so the engines' host cost can be compared
directly.
"""
from __future__ import annotations

import dataclasses
import heapq
import time as _time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.synthetic import (
    SyntheticImageTask,
    batch_iterator,
    partition_dirichlet,
    partition_noniid,
)
from repro.models.cnn import (
    CNNConfig,
    build_unit_space,
    cnn_apply,
    cnn_block_compute,
    cnn_flops,
    cnn_flops_from_shapes,
    extract_bn_scales,
    init_cnn,
    vgg_config,
)

from .aggregation import (
    AsyncServer,
    RobustAggConfig,
    aggregate_by_unit,
    aggregate_by_unit_stacked,
    aggregate_by_worker,
    aggregate_by_worker_stacked,
    coordinate_mask,
    embed_params,
    extract_subparams,
    noise_key,
    robust_submission_step_jnp,
    roundtrip_total,
    subparam_shapes,
    tally_roundtrip,
)
from .fleet import FleetEngine, FleetJob
from .importance import (
    CIG_METHODS,
    METHODS,
    ImportanceContext,
    grad_magnitude_scores,
)
from .masks import (
    full_index,
    is_nested,
    payload_bytes,
    prune_to_budget,
    regrow_index,
    retention,
    similarity,
)
from .faults import fault_ledger
from .pruned_rate import PrunedRateConfig, WorkerHistory, learn_pruned_rates
from .scenario import (
    AsyncEventPlan,
    ScenarioConfig,
    ScenarioEngine,
    full_participation,
)
from .timing import HeterogeneityConfig, heterogeneity_from_times, make_bandwidths
from .worker import LocalTrainer, local_unit_stats, make_batch_plan, plan_steps

__all__ = [
    "SimConfig", "SimResult", "RegrowConfig", "run_simulation", "default_cnn",
]

_DATA_DEP_IMPORTANCE = ("l1", "taylor", "fpgm", "hrank")


def default_cnn() -> CNNConfig:
    """Small VGG used by the CPU-budget simulations (same family as VGG16)."""
    return vgg_config("vgg_sim", [32, "M", 64, "M", 64], num_classes=10, image_size=16)


@dataclasses.dataclass(frozen=True)
class RegrowConfig:
    """FedDST-style mask readjustment (arXiv:2112.09824; ROADMAP item 4).

    Every ``interval`` rounds, each worker with retention < 1 prunes
    ``alpha_t`` of its retained parameters by GLOBAL weight magnitude, then
    grows the exact same parameter budget back from its absent units, ranked
    by gradient magnitude of the dense model at the aggregated global on the
    worker's own shard (the RigL/FedDST grow signal — pruned slots carry
    real gradients there).  ``alpha_t`` follows FedDST's cosine anneal
    ``0.5 * alpha0 * (1 + cos(pi * (t-1) / T))`` (``schedule="cosine"``) or
    stays at ``alpha0`` (``schedule="constant"``).

    Readjustment happens at the START of a round, BEFORE broadcast-back, so
    grown units inherit their global values for free on the resident engines
    (``theta_g[None] * M`` scatters into the fresh mask) — a mask-row
    rewrite with zero recompiles.  Retention is ~unchanged (the grow budget
    equals the shrink's removed mass, overshoot < one unit cost), so Alg. 2
    pruned-rate histories keep monotone gammas up to that sliver — the
    recency-capped Newton guard absorbs the rest."""

    interval: int = 4          # R_adj: rounds between mask readjustments
    alpha0: float = 0.3        # initial readjust fraction
    schedule: str = "cosine"   # "cosine" | "constant"

    def __post_init__(self):
        if self.interval < 1:
            raise ValueError(f"regrow interval {self.interval} must be >= 1")
        if not (0.0 < self.alpha0 < 1.0):
            raise ValueError(f"regrow alpha0 {self.alpha0} outside (0, 1)")
        if self.schedule not in ("cosine", "constant"):
            raise ValueError(
                f"regrow schedule {self.schedule!r} not in cosine/constant"
            )


@dataclasses.dataclass
class SimConfig:
    method: str = "adaptcl"
    rounds: int = 30
    num_workers: int = 10
    local_epochs: float = 1.0
    batch_size: int = 32
    lr: float = 0.05
    lam: float = 1e-4                   # group-lasso coefficient (sparse train)
    prune_interval: int = 5             # PI (paper: 10, T=150; scaled T=30)
    beta: float = 1.0                   # pruning position within local epochs
    importance: str = "cig_bnscalor"
    aggregation: str = "by_worker"
    rate_cfg: PrunedRateConfig = dataclasses.field(default_factory=PrunedRateConfig)
    het: HeterogeneityConfig = dataclasses.field(default_factory=HeterogeneityConfig)
    t_train_full: float = 1.0           # seconds per local round, full model
    train_sens: float = 0.1             # Appendix E: GPU-like ~0, CPU-like ~1
    time_jitter: float = 0.02
    noniid_s: float = 0.0               # paper's s%: 0 (IID) or 80
    ssp_threshold: int = 2
    fedasync_a: float = 0.5
    dcasgd_lambda: float = 2.0
    dcasgd_m: float = 0.95
    fixed_pruned_rates: Optional[List[List[float]]] = None  # Tab. IX mode
    # AdaptCL+DGC (Appendix E / Tab. XVII): commit only the largest
    # (1-sparsity) fraction of each weight delta; the rest accumulates
    # locally until it crosses the threshold (momentum-factor-masking lite).
    dgc_sparsity: float = 0.0
    # FedDST-style mask regrowth (RegrowConfig); None = monotone pruning
    # only.  Applies to the synchronous methods under every engine; regrow
    # rounds cut fused chunks so the readjustment runs at a host boundary.
    regrow: Optional[RegrowConfig] = None
    # local-training engine: "sequential" | "bucketed" | "masked" | "fused"
    # (core.fleet; "fused" = the resident stacks PLUS chunked on-device
    # round fusion, core.fused)
    engine: str = "sequential"
    # fused engine: max rounds per lax.scan chunk (0 = auto: fuse up to the
    # next host boundary — a prune-rate-learning event for adaptcl, 8 rounds
    # otherwise).  Chunks always end at learning events and churn rounds.
    round_fusion: int = 0
    # opt-in cross-round momentum: the resident momentum stack becomes a
    # true optimizer carry across phases AND rounds (masked/fused engines
    # only) instead of the per-phase zero restart of the reference engines
    resident_momentum: bool = False
    # device compute path of the masked engine's programs: "dense" executes
    # base-shape convs under 0/1 masks (full FLOPs), "block_skip" dispatches
    # convs + head through kernels.pruned_matmul so device FLOPs track
    # retention (requires engine="masked"; Pallas interpreter on CPU)
    compute: str = "dense"
    # pruned_matmul tile sizes (block_m, block_n, block_k); multiples of 128
    # on TPU (enforced), smaller only for CPU/interpret runs and small models
    compute_blocks: Tuple[int, int, int] = (128, 128, 128)
    # client sampling / dropout / churn (core.scenario); async methods
    # honour sampling + dropout (timed-out commits) and reject churn
    scenario: Optional[ScenarioConfig] = None
    # robust server aggregation (core.aggregation.RobustAggConfig): per-commit
    # L2 norm clipping, coordinate-wise trimmed mean, and the MAD-outlier
    # quarantine health tracker.  by_worker aggregation only; async methods
    # support clip + quarantine and reject trim by name.  None = the plain
    # capability-weighted mean, bit-identical to pre-feature.
    robust: Optional[RobustAggConfig] = None
    # async engines: event-queue commits landing within this virtual window
    # batch into ONE fleet call (0.0 = serial, exactly the legacy behavior)
    async_window: float = 0.0
    # mesh-sharded fleet (fused sync engine only): a 1-D device mesh with a
    # ``fleet_axis`` axis (launch.mesh.make_fleet_mesh) shards every
    # resident [W, ...] stack as W = n_dev x W_local and runs each scan
    # chunk as one program PER SHARD with on-mesh two-tier aggregation
    # (core.fused / sharding.specs.fleet_sharding).  None = single device.
    mesh: Optional[object] = None
    fleet_axis: str = "fleet"
    cnn: CNNConfig = dataclasses.field(default_factory=default_cnn)
    task: Optional[SyntheticImageTask] = None
    eval_every: int = 1
    seed: int = 0


@dataclasses.dataclass
class SimResult:
    method: str
    acc_time: List[Tuple[float, float]]         # (virtual seconds, test acc)
    final_acc: float
    best_acc: float
    best_acc_time: float
    total_time: float
    het_traj: List[Tuple[int, float]]            # (round, H of update times)
    retentions: List[float]                      # final gamma per worker
    param_reduction: float                       # avg over workers
    flops_reduction: float
    comm_bytes: float
    server_overhead_s: float                     # Alg.2 + aggregation walltime
    recompiles: int
    similarity_traj: List[Tuple[int, float]]     # Eq. 3 between two workers
    update_times: List[List[float]]              # per round, per worker
    engine: str = "sequential"                   # fleet engine that ran it
    batched_calls: int = 0                       # vmapped device programs
    walltime_s: float = 0.0                      # host wall-clock of the run
    host_roundtrips: int = 0                     # extract/embed in round loop
    # (round, n_active, n_dropped, n_joined) per round when a scenario ran
    scenario_rounds: List[Tuple[int, int, int, int]] = dataclasses.field(
        default_factory=list
    )
    # sub-stack row buckets launched by the resident engine (sorted); the
    # recompile count is bounded by len(bucket_sizes) x phases
    bucket_sizes: List[int] = dataclasses.field(default_factory=list)
    # device compute path ("dense" | "block_skip") + the training-FLOPs
    # ledger: flops_ideal is the paper's per-sub-model cost
    # (cnn_flops_from_shapes of each worker's reconfigured shapes x images
    # trained), flops_executed the per-worker dispatched cost — equal to
    # ideal for physically reconfigured engines, the full base-shape cost
    # for masked+dense, and the block-granular proxy
    # (models.cnn.cnn_block_compute) for masked+block_skip.  blocks_executed
    # counts kernel grid cells whose MXU pass runs (the interpret-mode proxy
    # benches assert on).  The ledger counts each worker's SCHEDULED plan
    # steps x batch images; the resident engine's compute-and-discard padding
    # (step pads to the per-phase max, pow2 bucket-row pads) is excluded —
    # identical across compute paths, so ratios between them are unaffected.
    compute: str = "dense"
    # True when the block-skip kernel ran in the Pallas interpreter (CPU),
    # False when it was compiled by Mosaic (TPU) or never ran
    compute_interpret: bool = False
    flops_executed: float = 0.0
    flops_ideal: float = 0.0
    blocks_executed: float = 0.0
    # steady-state per-image cost at the FINAL sub-models (mean over workers)
    # — what a post-prune training step executes, free of warm-up rounds
    flops_per_image_final: float = 0.0
    blocks_per_image_final: float = 0.0
    # jitted training/round programs LAUNCHED (one per device dispatch): the
    # resident engine pays O(rounds) of these, the fused engine
    # O(rounds / round_fusion) — the companion metric to host_roundtrips
    host_dispatches: int = 0
    # wall spent inside FIRST calls of each compiled signature (trace +
    # compile + one execution) — subtract from walltime_s for steady-state
    compile_walltime_s: float = 0.0
    # fused engine: number of lax.scan chunk programs launched
    fused_chunks: int = 0
    # mesh the run executed on (SimConfig.mesh): devices the resident
    # [W, ...] stacks were found on at the end of a fused run (read off the
    # arrays' shardings, not the config), fleet-axis extent, and the stack
    # PartitionSpec — 1/1/None on single-device runs
    n_devices: int = 1
    fleet_axis_size: int = 1
    shard_spec: Optional[str] = None
    # every pruning event: (round, worker, {layer: retained unit ids}) —
    # what the cross-engine bit-identity tests compare round-by-round
    prune_events: List[Tuple[int, int, Dict[str, tuple]]] = dataclasses.field(
        default_factory=list
    )
    # fault-injection ledger (core.faults.fault_ledger): all zeros on
    # fault-free runs; identical across engines under the same fault stream
    # since every engine derives it from the one shared event sequence
    drift_events: int = 0        # drift-multiplier changes (re-learning triggers)
    rounds_degraded: int = 0     # rounds aggregating a fault-reduced cohort
    rounds_skipped: int = 0      # rounds skipped: submitters < min_participants
    workers_recovered: int = 0   # offline->online transitions
    retry_total: int = 0         # re-join rounds trained without aggregation
    byz_commits: int = 0         # submitted commits from compromised workers
    lost_commits: int = 0        # channel drops surviving every retry
    dup_commits: int = 0         # delivered commits duplicated by the channel
    corrupt_commits: int = 0     # delivered commits with garbled payloads
    # robust-aggregation observability: commits excluded by the quarantine
    # health tracker (sync: quarantined submitter-rounds; async: rejected
    # commits) — 0 whenever SimConfig.robust has no quarantine
    quarantined_commits: int = 0
    # final global model (base coordinates) — test/analysis hook
    global_params: Optional[Dict[str, np.ndarray]] = None


def _env_accuracy(env: "_Env", params) -> float:
    """Test accuracy of a base-shape global model through the trainer's jit
    cache: one compiled program per test-batch shape instead of op-by-op
    dispatch (which paid an untracked trace+compile tax on every run).
    Counted like any other dispatch, so ``host_dispatches`` and
    ``compile_walltime_s`` stay honest across engines."""
    cfg = env.sim.cnn
    x, y = env.task.x_test, env.task.y_test
    jp = {k: jnp.asarray(v) for k, v in params.items()}
    correct = 0
    for i in range(0, len(x), 256):
        xb = x[i : i + 256]
        logits = env.trainer._call_cached(
            ("eval_logits", xb.shape),
            lambda: jax.jit(lambda p, q: cnn_apply(p, cfg, q)),
            jp, jnp.asarray(xb),
            count_compile=False,
        )
        correct += int((np.argmax(np.asarray(logits), -1) == y[i : i + 256]).sum())
    return correct / len(x)


class _Env:
    """Shared experimental fixture (same across all methods, per seed)."""

    def __init__(self, sim: SimConfig):
        self.sim = sim
        if sim.compute == "block_skip" and sim.engine != "masked":
            raise ValueError(
                "compute='block_skip' needs the masked (resident) engine — "
                "the block-keep flags are derived from the 0/1 mask stacks; "
                "the reconfigured engines already run physically small "
                "models, and the fused engine's scan does not carry the "
                "interpret-mode kernel off-TPU"
            )
        if sim.resident_momentum and sim.engine not in ("masked", "fused"):
            raise ValueError(
                "resident_momentum needs a resident engine "
                "(engine='masked' or 'fused') — the cross-round carry IS "
                "the FleetState momentum stack"
            )
        if sim.regrow is not None and sim.method not in (
            "adaptcl", "fedavg", "fedavg_s"
        ):
            raise ValueError(
                "SimConfig.regrow (FedDST mask readjustment) applies to the "
                "synchronous methods only — async workers never prune, so "
                "there is nothing to regrow"
            )
        if sim.mesh is not None and (
            sim.engine != "fused"
            or sim.method not in ("adaptcl", "fedavg", "fedavg_s")
        ):
            raise ValueError(
                "SimConfig.mesh (the mesh-sharded fleet) requires the fused "
                "SYNC engine (engine='fused', method in adaptcl/fedavg/"
                "fedavg_s) — the sharded path is the per-shard lax.scan "
                "chunk program with on-mesh aggregation (core.fused)"
            )
        if sim.robust is not None and sim.aggregation != "by_worker":
            raise ValueError(
                "SimConfig.robust (clip/trimmed-mean/quarantine) requires "
                "aggregation='by_worker' — the robust layer defends "
                "per-worker commit deltas, and by_unit's per-coordinate "
                f"holder counts have no delta to clip; got "
                f"aggregation={sim.aggregation!r}"
            )
        _flts = (
            sim.scenario.faults
            if sim.scenario is not None and sim.scenario.faults is not None
            else None
        )
        if _flts is not None and sim.aggregation != "by_worker":
            for fam in ("byzantine", "channel"):
                if getattr(_flts, fam, None) is not None:
                    raise ValueError(
                        f"FaultConfig.{fam} perturbs per-worker commit "
                        "deltas and requires aggregation='by_worker'; got "
                        f"aggregation={sim.aggregation!r}"
                    )
        skew = sim.scenario.skew if sim.scenario is not None else None
        if skew is not None and sim.noniid_s > 0.0:
            raise ValueError(
                "ScenarioConfig.skew (Dirichlet label concentration) and "
                f"SimConfig.noniid_s={sim.noniid_s} are competing Non-IID "
                "partitioners — set exactly one"
            )
        self.task = sim.task or SyntheticImageTask(
            num_classes=sim.cnn.num_classes, image_size=sim.cnn.image_size,
            train_size=1280, test_size=512, seed=sim.seed,
        )
        self.shards = (
            partition_dirichlet(
                self.task.y_train, sim.num_workers, skew, seed=sim.seed
            )
            if skew is not None
            else partition_noniid(
                self.task.y_train, sim.num_workers, sim.noniid_s, seed=sim.seed
            )
        )
        key = jax.random.PRNGKey(sim.seed)
        self.base_params = {k: np.asarray(v) for k, v in init_cnn(key, sim.cnn).items()}
        self.base_shapes = {k: v.shape for k, v in self.base_params.items()}
        self.space, self.unit_map = build_unit_space(sim.cnn, self.base_params)
        self.full_bytes = payload_bytes(full_index(self.space), self.space)
        self.full_flops = cnn_flops(self.base_params, sim.cnn)
        self.bandwidths = make_bandwidths(sim.het, self.full_bytes, sim.t_train_full)
        self.trainer = LocalTrainer(
            sim.cnn, lr=sim.lr,
            compute=sim.compute, compute_blocks=sim.compute_blocks,
        )
        self.fleet = FleetEngine(
            self.trainer, self.unit_map, self.base_shapes, engine=sim.engine
        )
        self.rng = np.random.default_rng(sim.seed + 17)
        # training-FLOPs ledger (SimResult.flops_*): per-image costs are
        # cached per distinct global index, multiplied by images trained
        self.flops_executed = 0.0
        self.flops_ideal = 0.0
        self.blocks_executed = 0.0
        self._acct_cache: Dict[tuple, Tuple[float, float, float]] = {}

    def cost_for_index(self, index) -> Tuple[float, float, float]:
        """(executed flops, ideal flops, executed kernel blocks) per IMAGE at
        this global index, for the engine/compute path this run dispatches."""
        key = tuple(
            (l, tuple(map(int, v))) for l, v in sorted(index.items())
        )
        cached = self._acct_cache.get(key)
        if cached is None:
            shapes = subparam_shapes(index, self.unit_map, self.base_shapes)
            ideal = cnn_flops_from_shapes(shapes, self.sim.cnn)
            if self.sim.compute == "block_skip":
                masks = {
                    l.name: np.asarray(
                        np.isin(np.arange(l.num_units), index[l.name]), np.float32
                    )
                    for l in self.space.layers
                }
                bc = cnn_block_compute(self.sim.cnn, masks, self.sim.compute_blocks)
                cached = (bc["flops"], ideal, bc["blocks"])
            elif self.sim.engine in ("masked", "fused"):
                # dense masked programs run the base shapes regardless of masks
                cached = (self.full_flops, ideal, 0.0)
            else:
                # physically reconfigured models execute exactly their size
                cached = (ideal, ideal, 0.0)
            self._acct_cache[key] = cached
        return cached

    def account_train(self, index, steps: int):
        """Record one worker's local-training phase in the FLOPs ledger:
        ``steps`` plan steps x batch images, costed at this global index
        (scheduled work only — the resident engine's compute-and-discard
        step/bucket padding is not attributed to any worker)."""
        if steps <= 0:
            return
        executed, ideal, blocks = self.cost_for_index(index)
        images = steps * self.sim.batch_size
        self.flops_executed += images * executed
        self.flops_ideal += images * ideal
        self.blocks_executed += images * blocks

    def phi(self, worker: int, params, payload_factor: float = 1.0) -> float:
        """Channel-model update time for this worker's current sub-model."""
        return self._phi_from_shapes(
            worker, {k: v.shape for k, v in params.items()}, payload_factor
        )

    def phi_from_index(
        self, worker: int, index, payload_factor: float = 1.0, jitter: bool = True,
        time_mult: float = 1.0,
    ) -> float:
        """Channel-model time from the global index alone — the resident
        engine's path: payload bytes and FLOPs derive from the reconfigured
        SHAPES (``subparam_shapes``), no arrays are materialized."""
        return self._phi_from_shapes(
            worker,
            subparam_shapes(index, self.unit_map, self.base_shapes),
            payload_factor,
            jitter,
            time_mult,
        )

    def _phi_from_shapes(
        self, worker, shapes, payload_factor, jitter=True, time_mult=1.0
    ) -> float:
        sim = self.sim
        bytes_raw = sum(int(np.prod(s)) * 4 for s in shapes.values())
        flops_w = cnn_flops_from_shapes(shapes, sim.cnn)
        jmult = (
            float(np.exp(self.rng.normal(0, sim.time_jitter)))
            if jitter and sim.time_jitter > 0 else 1.0
        )
        # capability drift folds into the same multiplicative slot as the
        # jitter, so the fused path (which pre-draws jitters and multiplies
        # the drift curve in on host) reproduces the product bit for bit
        return self.phi_from_cost(
            worker, bytes_raw, flops_w, payload_factor, jmult * time_mult
        )

    def phi_from_cost(
        self, worker: int, bytes_raw: int, flops_w: float,
        payload_factor: float = 1.0, jitter_mult: float = 1.0,
    ) -> float:
        """The Eq. 6/7 channel model from precomputed payload bytes + FLOPs.

        The ONE implementation behind both the lazy per-round path
        (``_phi_from_shapes``, which derives the costs from shapes and draws
        its jitter) and the fused engine's cached path (costs memoized per
        retained-count signature, jitter pre-drawn) — so the two can't
        drift and clocks stay engine-identical."""
        sim = self.sim
        bytes_w = payload_factor * bytes_raw
        rel = flops_w / self.full_flops
        t_train = sim.t_train_full * ((1 - sim.train_sens) + sim.train_sens * rel)
        t = 2.0 * bytes_w / self.bandwidths[worker] + t_train * sim.local_epochs
        return t * jitter_mult

    def shard_xy(self, w):
        sh = self.shards[w]
        return self.task.x_train[sh], self.task.y_train[sh]


# ---------------------------------------------------------------------------
# synchronous methods: fedavg / fedavg_s / adaptcl
# ---------------------------------------------------------------------------

def _dgc_compress(delta: Dict[str, np.ndarray], residual: Dict[str, np.ndarray],
                  sparsity: float):
    """Top-|.| delta sparsification with local residual accumulation ([11]).

    Returns (committed delta, new residual, kept-fraction payload factor).

    A reconfiguration that changed a tensor's shape restarts DGC's
    accumulators for it (momentum-factor-masking semantics): the stale
    residual is dropped AND the tensor commits densely this round, so the
    kept-fraction accounting is reset too — the payload factor honestly
    reflects the dense warm-up commit instead of silently reporting the
    steady-state sparsity."""
    committed, new_res = {}, {}
    kept = total = 0
    for k, d in delta.items():
        r = residual.get(k)
        restarted = r is not None and r.shape != d.shape
        if r is not None and not restarted:
            d = d + r
        if restarted:
            committed[k], new_res[k] = d, np.zeros_like(d)
            kept += d.size
            total += d.size
            continue
        flat = np.abs(d).ravel()
        # keep budget in float32 — the SAME rounding the device compressor
        # (aggregation.dgc_compress_jnp) performs, so keep sets can't diverge
        # on half-integer budgets
        n_keep = max(
            1, int(np.round(np.float32(flat.size) * np.float32(1.0 - sparsity)))
        )
        if n_keep >= flat.size:
            committed[k], new_res[k] = d, np.zeros_like(d)
            kept += flat.size
        else:
            thr = np.partition(flat, flat.size - n_keep)[flat.size - n_keep]
            mask = np.abs(d) >= thr
            committed[k] = d * mask
            new_res[k] = d * (1.0 - mask)
            # ties at the threshold all commit (>=), so count the REALIZED
            # mask — n_keep undercounts exactly when |delta| values collide
            kept += int(mask.sum())
        total += flat.size
    # payload: kept values + their indices (~1.25x values, as in DGC)
    return committed, new_res, 1.25 * kept / max(total, 1)


def _dgc_compress_stacked(
    delta: Dict[str, np.ndarray],        # {path: [W, ...]} base-coord deltas
    residual: Dict[str, np.ndarray],     # {path: [W, ...]} accumulators
    sparsity: float,
    masks: Optional[Dict[str, np.ndarray]] = None,   # {path: [W, ...]} 0/1
    rows: Optional[np.ndarray] = None,               # bool [W]: rows to commit
):
    """Vectorized DGC over the resident ``[W, ...]`` delta stacks.

    Per tensor, the top-|.| threshold is computed per worker row in one
    ``np.sort`` over the flattened ``[W, N]`` view.  ``masks`` makes the
    compressor mask-aware: each worker's keep budget is a fraction of its
    RETAINED coordinate count (matching the per-worker compressor applied to
    the reconfigured tensor), pruned coordinates are never committed, and the
    residual is kept only on retained coordinates (pruning zeroes a worker's
    residual on the units it lost — nothing else restarts, unlike the
    shape-changing per-worker path, because resident shapes never change).
    ``rows`` limits commits to the submitting workers; others keep their
    residual untouched and report payload factor 1.0.

    Returns (committed stacks, new residual stacks, factors ``[W]``)."""
    W = next(iter(delta.values())).shape[0]
    rows = np.ones(W, bool) if rows is None else np.asarray(rows, bool)
    committed: Dict[str, np.ndarray] = {}
    new_res: Dict[str, np.ndarray] = {}
    kept = np.zeros(W)
    total = np.zeros(W)
    for k, d in delta.items():
        r = residual.get(k)
        acc = d if r is None else d + r
        flat = acc.reshape(W, -1)
        absf = np.abs(flat)
        if masks is not None:
            valid = masks[k].reshape(W, -1) > 0
            sizes = valid.sum(axis=1)
            absf = np.where(valid, absf, -1.0)
        else:
            valid = None
            sizes = np.full(W, flat.shape[1])
        # float32 keep budgets, matching aggregation.dgc_compress_jnp exactly
        n_keep = np.maximum(
            1,
            np.round(
                sizes.astype(np.float32) * np.float32(1.0 - sparsity)
            ).astype(np.int64),
        )
        n_keep = np.minimum(n_keep, np.maximum(sizes, 1))
        order = np.sort(absf, axis=1)[:, ::-1]
        thr = order[np.arange(W), n_keep - 1]
        keep = absf >= thr[:, None]
        if valid is not None:
            keep &= valid
        com = np.where(keep, flat, 0.0)
        res = np.where(keep, 0.0, flat)
        if valid is not None:
            res = np.where(valid, res, 0.0)
        old_res = np.zeros_like(flat) if r is None else r.reshape(W, -1)
        rowsf = rows[:, None]
        committed[k] = np.where(rowsf, com, 0.0).reshape(d.shape).astype(d.dtype)
        new_res[k] = np.where(rowsf, res, old_res).reshape(d.shape).astype(d.dtype)
        # realized per-row commit counts: ties at the threshold all pass the
        # >= test, and a fully-masked row (sizes == 0) commits nothing — the
        # keep mask already reflects both, n_keep reflects neither
        kept += np.where(rows, keep.sum(axis=1), 0)
        total += np.where(rows, sizes, 0)
    factors = np.where(rows, 1.25 * kept / np.maximum(total, 1), 1.0)
    return committed, new_res, factors


def _regrow_alpha(cfg: RegrowConfig, t: int, rounds: int) -> float:
    """Readjust fraction in force at the start of round t (FedDST anneal)."""
    if cfg.schedule == "constant":
        return cfg.alpha0
    return float(
        0.5 * cfg.alpha0 * (1.0 + np.cos(np.pi * (t - 1) / max(rounds, 1)))
    )


def _regrow_round(sim: SimConfig, t: int) -> bool:
    """Does a mask readjustment fire at the START of round t?  Every
    ``interval`` completed rounds — so the first possible event is the start
    of round ``interval + 1``, operating on a freshly aggregated global."""
    return (
        sim.regrow is not None
        and t > 1
        and (t - 1) % sim.regrow.interval == 0
    )


def _weight_magnitude_scores(params, unit_map, unit_counts) -> Dict[str, np.ndarray]:
    """Per-unit L2 group norms of a base-coordinate param dict (float64) —
    the shrink half of the readjustment ranks retained units by the GLOBAL
    model's weight magnitude, so the order is one shared host computation
    per regrow round, identical for every engine."""
    acc = {k: np.zeros(n, np.float64) for k, n in unit_counts.items()}
    for path, entries in unit_map.items():
        arr = params.get(path)
        if arr is None:
            continue
        sq = np.asarray(arr, np.float64) ** 2
        for lname, axis in entries:
            if lname not in acc:
                continue
            axes = tuple(i for i in range(sq.ndim) if i != axis)
            acc[lname] += sq.sum(axis=axes)
    return {k: np.sqrt(v) for k, v in acc.items()}


def _regrow_step(
    sim: SimConfig, env: _Env, global_params, indices, t: int
) -> List[Tuple[int, Dict[str, np.ndarray]]]:
    """One FedDST mask readjustment at the start of round t (host math).

    Per worker with retention < 1: ``prune_to_budget`` removes ``alpha_t``
    of the retained parameter mass by global weight magnitude, then
    ``regrow_index`` adds the SAME integer parameter budget back from the
    absent units, ranked by |grad| of the dense model at the global on the
    worker's shard head (``trainer.gradient`` — one extra jit signature,
    cached across all regrow events).  Consumes NO ``env.rng`` draws, so
    the plan/jitter streams — and therefore everything a regrow-disabled
    run computes — are untouched.

    Returns ``[(worker, new_index)]`` for the readjusted workers; the
    caller records them in ``prune_events`` and refreshes device masks."""
    cfg = sim.regrow
    alpha_t = _regrow_alpha(cfg, t, sim.rounds)
    if alpha_t <= 0.0:
        return []
    shrink_scores = None
    out: List[Tuple[int, Dict[str, np.ndarray]]] = []
    for w in range(sim.num_workers):
        if retention(indices[w], env.space) >= 1.0:
            continue   # full model: no absent units to grow back
        if shrink_scores is None:
            shrink_scores = _weight_magnitude_scores(
                global_params, env.unit_map, env.space.unit_counts
            )
        shrunk = prune_to_budget(indices[w], shrink_scores, alpha_t, env.space)
        budget = sum(
            (len(indices[w][l.name]) - len(shrunk[l.name])) * l.unit_param_cost
            for l in env.space.layers
        )
        if budget <= 0:
            continue
        x, y = env.shard_xy(w)
        grads = env.trainer.gradient(
            {k: np.asarray(v, np.float32) for k, v in global_params.items()},
            env.unit_map, x[:64], y[:64],
        )
        grow_scores = grad_magnitude_scores(
            grads, env.unit_map, env.space.unit_counts
        )
        indices[w] = regrow_index(shrunk, grow_scores, budget, env.space)
        out.append((w, indices[w]))
    return out


def _skip_round_time(env: _Env, scen: ScenarioEngine, indices, round_t: int) -> float:
    """Virtual-clock advance for a SKIPPED round (too few fault survivors to
    aggregate): the server waits out the full straggler deadline —
    ``timeout_factor`` x the slowest nominal update time at the current
    sub-models — then moves on.  Jitter-free and RNG-free, so the lazy and
    fused engines advance identical clocks without consuming any stream."""
    mults = scen.drift_mults(round_t)
    phis = [
        env.phi_from_index(w, indices[w], jitter=False, time_mult=float(mults[w]))
        for w in range(len(indices))
    ]
    return scen.cfg.timeout_factor * max(phis)


def _commit_multiplicity(events) -> np.ndarray:
    """Per-worker commit weight: submit x delivered x (1 + dup), host f64.

    With no channel model this IS the submitter indicator, so dividing by
    its sum reproduces the pre-feature plain-mean weights bit-for-bit."""
    mult = events.submitters.astype(np.float64)
    if events.delivered is not None:
        mult = mult * events.delivered * (1.0 + events.dup)
    return mult


def _robust_aggregate_host(
    agg_stacks, mask_stacks, global_params, mult, events,
    byz_cfg, ch_cfg, corrupt_on, rb_cfg, seed: int, t: int,
    strikes, quar_left,
):
    """Masked-loop twin of the fused robust branch.

    Calls THE same :func:`robust_submission_step_jnp` the fused scan body
    runs, eagerly, on host-fed ``[W, ...]`` stacks — attack transform,
    channel corruption, clip/trim/quarantine and the wsum==0 all-lost-round
    guard are one code path, so robust worlds keep masked == fused by
    construction.  Returns ``(new_global_np, strikes', quar_left',
    quar_now_bool_or_None)``."""
    quar_cfg = rb_cfg.quarantine if rb_cfg is not None else None
    stacks = {
        k: jnp.asarray(np.asarray(v, np.float32)) for k, v in agg_stacks.items()
    }
    masks = (
        {k: jnp.asarray(np.asarray(v, np.float32)) for k, v in mask_stacks.items()}
        if mask_stacks is not None else None
    )
    gl = {
        k: jnp.asarray(np.asarray(v, np.float32))
        for k, v in global_params.items()
    }
    ms = mult.sum()
    weights = (
        (mult / ms).astype(np.float32) if ms > 0
        else np.zeros_like(mult, dtype=np.float32)
    )
    byz_row = None
    if byz_cfg is not None and events.byz is not None:
        byz_row = jnp.asarray(events.byz & events.submitters)
    cor_row = None
    if corrupt_on and events.corrupt is not None:
        cor_row = jnp.asarray(events.corrupt & events.delivered & events.submitters)
    new_g, st2, qu2, quar_now = robust_submission_step_jnp(
        stacks, masks, gl, jnp.asarray(mult.astype(np.float32)),
        jnp.asarray(weights), byz_row, cor_row,
        noise_key(seed + 51721, t) if byz_cfg is not None else None,
        noise_key(seed + 51722, t) if corrupt_on else None,
        strikes, quar_left,
        byz_mode=byz_cfg.mode if byz_cfg is not None else "sign_flip",
        byz_scale=byz_cfg.scale if byz_cfg is not None else -10.0,
        byz_noise_std=byz_cfg.noise_std if byz_cfg is not None else 1.0,
        corrupt_std=ch_cfg.corrupt_std if corrupt_on else 10.0,
        clip=rb_cfg.clip if rb_cfg is not None else None,
        trim=rb_cfg.trim if rb_cfg is not None else 0.0,
        quarantine=quar_cfg,
    )
    out = {k: np.asarray(v) for k, v in new_g.items()}
    quar_np = np.asarray(quar_now) > 0.5 if quar_cfg is not None else None
    return out, st2, qu2, quar_np


def _run_sync(sim: SimConfig, env: _Env) -> SimResult:
    W = sim.num_workers
    sparse = sim.method in ("fedavg_s", "adaptcl")
    adapt = sim.method == "adaptcl"
    lam = sim.lam if sparse else 0.0
    resident = sim.engine == "masked"
    scen = ScenarioEngine(sim.scenario, W) if sim.scenario is not None else None
    # robust-aggregation statics: byzantine / lossy channel / clip-trim-
    # quarantine.  All None => every branch below is the pre-feature one.
    faults_cfg = (
        sim.scenario.faults
        if sim.scenario is not None and sim.scenario.faults is not None
        else None
    )
    byz_cfg = faults_cfg.byzantine if faults_cfg is not None else None
    ch_cfg = faults_cfg.channel if faults_cfg is not None else None
    corrupt_on = ch_cfg is not None and ch_cfg.corrupt > 0.0
    rb_cfg = (
        sim.robust if sim.robust is not None and sim.robust.any_active else None
    )
    quar_cfg = rb_cfg.quarantine if rb_cfg is not None else None
    robust_on = byz_cfg is not None or ch_cfg is not None or rb_cfg is not None
    rb_strikes = rb_quar = None
    if quar_cfg is not None:
        rb_strikes = jnp.zeros(W, jnp.int32)
        rb_quar = jnp.zeros(W, jnp.int32)
    quarantined_commits = 0
    dgc_residuals: List[Dict[str, np.ndarray]] = [{} for _ in range(W)]
    dgc_res_stack: Optional[Dict[str, np.ndarray]] = None

    global_params = dict(env.base_params)
    indices = [full_index(env.space) for _ in range(W)]
    histories = [WorkerHistory() for _ in range(W)]
    pending_rates = [0.0] * W
    cig_scores = None              # frozen at first pruning (CIG principle)
    interval_phis: List[List[float]] = [[] for _ in range(W)]
    prune_round_count = 0
    prune_events: List[Tuple[int, int, Dict[str, tuple]]] = []

    state = None
    pad_a = pad_b = None
    if resident:
        shard_x, shard_y = zip(*(env.shard_xy(w) for w in range(W)))
        state = env.fleet.init_state(env.base_params, list(shard_x), list(shard_y))
        if sim.resident_momentum:
            env.fleet.init_momentum(state)
        # constant per-phase step pads (churn keeps shard sizes fixed): every
        # gathered sub-stack shares one plan shape per phase, so recompiles
        # are bounded by the row buckets alone
        pad_a = max(
            plan_steps(len(env.shards[w]), sim.batch_size, sim.local_epochs)
            for w in range(W)
        )
        pad_b = max(
            plan_steps(
                len(env.shards[w]), sim.batch_size,
                (1 - sim.beta) * sim.local_epochs,
            )
            for w in range(W)
        )
        if sim.dgc_sparsity > 0.0:
            dgc_res_stack = {
                k: np.zeros((W,) + tuple(s), np.float32)
                for k, s in env.base_shapes.items()
            }

    clock = 0.0
    comm_bytes = 0.0
    server_overhead = 0.0
    acc_time, het_traj, sim_traj, upd_times = [], [], [], []
    scen_rows: List[Tuple[int, int, int, int]] = []
    events_log: List = []
    acc0 = _env_accuracy(env, global_params)
    acc_time.append((0.0, acc0))
    rt_base = roundtrip_total()    # host extract/embed round-trips in the loop

    def _learn_rates(t: int, drift_trigger: bool):
        """One Alg. 2 server step (pruning-interval boundary OR a capability
        drift event).  Drift re-learning invalidates the drifted worker's
        (gamma, phi) history first — those pairs describe a capability that
        no longer exists — so it re-enters through the bootstrap path."""
        nonlocal prune_round_count, cig_scores, pending_rates, interval_phis
        prune_round_count += 1
        if cig_scores is None and sim.importance == "cig_bnscalor":
            cig_scores = METHODS["cig_bnscalor"](ImportanceContext(
                unit_counts=env.space.unit_counts,
                scales=extract_bn_scales(global_params, sim.cnn),
            ))
        if drift_trigger:
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
            rates = learn_pruned_rates(histories, gammas_now, phis_now, sim.rate_cfg)
        pending_rates = list(rates)
        interval_phis = [[] for _ in range(W)]

    for t in range(1, sim.rounds + 1):
        events = scen.draw(t) if scen is not None else full_participation(W)
        events_log.append(events)
        # --- churn: replaced slots restart as fresh full-model workers.
        if events.joined.any():
            for w in np.flatnonzero(events.joined):
                indices[w] = full_index(env.space)
                histories[w] = WorkerHistory()
                pending_rates[w] = 0.0
                dgc_residuals[w] = {}
                interval_phis[w] = []
                if dgc_res_stack is not None:
                    for k in dgc_res_stack:
                        dgc_res_stack[k][w] = 0.0
                env.shards[w] = scen.fresh_shard(
                    len(env.shards[w]), len(env.task.y_train)
                )
                if resident:
                    env.fleet.update_shard(state, int(w), *env.shard_xy(int(w)))
                    if sim.resident_momentum:
                        # a churned-in worker is a FRESH worker: its slot's
                        # cross-round velocity restarts at zero
                        state.momentum = {
                            k: v.at[int(w)].set(0.0)
                            for k, v in state.momentum.items()
                        }
            if resident:
                env.fleet.refresh_masks(state, indices)
        # --- crash recovery: a returning worker refetches the current global
        # (the ordinary broadcast-back covers that) and re-enters with its
        # LAST mask and history, but velocity/residuals accumulated against
        # pre-crash parameters restart at zero.
        if events.recovered is not None and events.recovered.any():
            rec_ws = [int(w) for w in np.flatnonzero(events.recovered)]
            for w in rec_ws:
                dgc_residuals[w] = {}
                if dgc_res_stack is not None:
                    for k in dgc_res_stack:
                        dgc_res_stack[k][w] = 0.0
            if resident and sim.resident_momentum:
                env.fleet.zero_momentum_rows(state, rec_ws)
        active_ws = [int(w) for w in np.flatnonzero(events.active)]
        if scen is not None:
            scen_rows.append((
                t, len(active_ws), int(events.dropped.sum()), int(events.joined.sum()),
            ))

        # --- FedDST mask readjustment at the round start, BEFORE
        # broadcast-back: grown units inherit their global values for free.
        # On the resident engine this is a pure mask-row rewrite.
        if _regrow_round(sim, t):
            regrown = _regrow_step(sim, env, global_params, indices, t)
            for w, idx_w in regrown:
                prune_events.append((
                    t, int(w),
                    {k: tuple(map(int, v)) for k, v in idx_w.items()},
                ))
            if resident and regrown:
                env.fleet.refresh_masks(state, indices)

        # --- graceful degradation floor: too few fault survivors to
        # aggregate.  Nothing trains, the global is untouched, and the
        # virtual clock waits out the straggler deadline — then the round
        # ends (no hang, no exception).  Server-side steps that do not need
        # submissions (Alg. 2 at an interval boundary, evals) still run, so
        # the fused engine's chunk boundaries see the same state.
        if events.skip:
            clock += _skip_round_time(env, scen, indices, t)
            upd_times.append([float("nan")] * W)
            t0 = _time.perf_counter()
            if adapt and (t % sim.prune_interval == 0 or events.drift_changed):
                _learn_rates(t, events.drift_changed)
            server_overhead += _time.perf_counter() - t0
            if t % sim.eval_every == 0:
                acc_time.append((clock, _env_accuracy(env, global_params)))
            continue

        # --- batch plans, drawn in worker order up front so the batch
        # sequences (and therefore the trained models) are identical across
        # engines.
        plans_a: List[Optional[np.ndarray]] = [None] * W
        plans_b: List[Optional[np.ndarray]] = [None] * W
        prune_now = [False] * W
        for w in active_ws:
            rate = pending_rates[w] if adapt else 0.0
            if adapt and rate > 0.0:
                e1, e2 = sim.beta * sim.local_epochs, (1 - sim.beta) * sim.local_epochs
                prune_now[w] = True
            else:
                e1, e2 = sim.local_epochs, 0.0
            n = len(env.shards[w])
            plans_a[w] = make_batch_plan(n, sim.batch_size, e1, env.rng)
            plans_b[w] = make_batch_plan(n, sim.batch_size, e2, env.rng)
        for w in active_ws:   # FLOPs ledger: phase A runs at the pre-prune index
            env.account_train(indices[w], plans_a[w].shape[0])

        # --- phase A: every participating worker's pre-prune local training,
        # ONE fleet call.  Resident path: broadcast-back is a masked scatter
        # into the [W, ...] stacks, then one vmapped program over the stack.
        worker_params: Dict[int, Dict[str, np.ndarray]] = {}
        if resident:
            env.fleet.scatter_global(state, global_params)
            env.fleet.train_rounds(
                state, plans_a, lam, pad_steps=pad_a,
                carry_momentum=sim.resident_momentum,
            )
        else:
            jobs_a = []
            for w in active_ws:
                x, y = env.shard_xy(w)
                jobs_a.append(FleetJob(
                    worker=w,
                    params=extract_subparams(global_params, indices[w], env.unit_map),
                    index=indices[w], x=x, y=y, plan=plans_a[w],
                ))
            for w, p in zip(active_ws, env.fleet.train_all(jobs_a, lam)):
                worker_params[w] = p

        # --- phase B: pruning workers prune/reconfigure at position beta,
        # then finish their remaining epochs (second fleet call).  Resident:
        # pruning only rewrites mask rows — shapes never change.
        jobs_b: List[FleetJob] = []
        pruned_any = False
        for w in active_ws:
            if not prune_now[w]:
                continue
            scores = _scores_for(
                sim, env, w, prune_round_count,
                worker_params.get(w), indices[w], cig_scores, state,
            )
            if resident:
                indices[w] = prune_to_budget(
                    indices[w], scores, pending_rates[w], env.space
                )
                pruned_any = True
            else:
                worker_params[w], indices[w] = env.trainer.prune_and_reconfigure(
                    worker_params[w], indices[w], scores, pending_rates[w],
                    env.space, env.unit_map,
                )
                if plans_b[w].shape[0] > 0:
                    x, y = env.shard_xy(w)
                    jobs_b.append(FleetJob(
                        worker=w, params=worker_params[w], index=indices[w],
                        x=x, y=y, plan=plans_b[w],
                    ))
            prune_events.append((
                t, int(w),
                {k: tuple(map(int, v)) for k, v in indices[w].items()},
            ))
        if resident:
            if pruned_any:
                env.fleet.refresh_masks(state, indices)
                env.fleet.train_rounds(
                    state,
                    [plans_b[w] if prune_now[w] else None for w in range(W)],
                    lam, pad_steps=pad_b,
                    carry_momentum=sim.resident_momentum,
                )
        elif jobs_b:
            for job, trained in zip(jobs_b, env.fleet.train_all(jobs_b, lam)):
                worker_params[job.worker] = trained
        for w in active_ws:   # FLOPs ledger: phase B runs at the pruned index
            if prune_now[w]:
                env.account_train(indices[w], plans_b[w].shape[0])

        # --- submission boundary: channel model + (optional) DGC delta
        # compression + aggregation inputs.
        submitters = events.submitters
        payload = np.ones(W)
        agg_stacks = None
        if resident:
            if sim.dgc_sparsity > 0.0:
                P = env.fleet.params_host(state)
                M = env.fleet.masks_host(state)
                deltas = {
                    k: P[k] - np.asarray(global_params[k], np.float32)[None] * M[k]
                    for k in P
                }
                committed, dgc_res_stack, payload = _dgc_compress_stacked(
                    deltas, dgc_res_stack, sim.dgc_sparsity,
                    masks=M, rows=submitters,
                )
                agg_stacks = {
                    k: np.asarray(global_params[k], np.float32)[None] * M[k]
                    + committed[k]
                    for k in P
                }
        else:
            for w in active_ws:
                if not submitters[w] or sim.dgc_sparsity <= 0.0:
                    continue
                received = extract_subparams(global_params, indices[w], env.unit_map)
                delta = {k: worker_params[w][k] - received[k] for k in worker_params[w]}
                committed_w, dgc_residuals[w], payload[w] = _dgc_compress(
                    delta, dgc_residuals[w], sim.dgc_sparsity
                )
                worker_params[w] = {k: received[k] + committed_w[k] for k in delta}

        phis = np.full(W, np.nan)
        dm = events.drift_mult
        for w in active_ws:
            pf = float(payload[w]) if submitters[w] else 1.0
            if resident:
                shapes_w = subparam_shapes(indices[w], env.unit_map, env.base_shapes)
            else:
                shapes_w = {k: v.shape for k, v in worker_params[w].items()}
            # channel retries stretch the drift factor FIRST (d*r), then the
            # jitter inside _phi_from_shapes — the fused engine associates
            # its floats the same way (j * (d * r)).
            retry_mult = 1.0
            if (ch_cfg is not None and events.retries is not None
                    and submitters[w]):
                retry_mult = (
                    1.0 + ch_cfg.retry_backoff * float(events.retries[w])
                )
            phi_w = env._phi_from_shapes(
                w, shapes_w, pf,
                time_mult=(float(dm[w]) if dm is not None else 1.0)
                * retry_mult,
            )
            phis[w] = phi_w
            interval_phis[w].append(phi_w)
            if submitters[w]:
                bytes_w = sum(int(np.prod(s)) * 4 for s in shapes_w.values())
                # lossy-channel accounting: every retry re-sends the upload,
                # a delivered duplicate arrives twice
                extra = 0.0
                if ch_cfg is not None and events.retries is not None:
                    extra = (
                        float(events.retries[w])
                        + float(events.dup[w] & events.delivered[w])
                    ) * pf * bytes_w
                comm_bytes += 2.0 * pf * bytes_w + extra
            pending_rates[w] = 0.0

        sub_phis = phis[submitters]
        round_time = float(sub_phis.max())
        if events.dropped.any() and scen is not None:
            # straggler timeout: the server waits out the deadline
            round_time *= scen.cfg.timeout_factor
        clock += round_time                     # BSP: slowest (received) gates
        upd_times.append(list(phis))
        het_traj.append((t, heterogeneity_from_times(sub_phis)))
        if W > 3:
            sim_traj.append((t, similarity(indices[1], indices[3])))

        t0 = _time.perf_counter()
        if resident:
            if agg_stacks is None:
                agg_stacks = env.fleet.params_host(state)
            if sim.aggregation == "by_unit":
                global_params = aggregate_by_unit_stacked(
                    agg_stacks, env.fleet.masks_host(state), submitters
                )
            elif robust_on:
                mult = _commit_multiplicity(events)
                global_params, rb_strikes, rb_quar, quar_now = (
                    _robust_aggregate_host(
                        agg_stacks, env.fleet.masks_host(state), global_params,
                        mult, events, byz_cfg, ch_cfg, corrupt_on, rb_cfg,
                        sim.seed, t, rb_strikes, rb_quar,
                    )
                )
                if quar_now is not None:
                    quarantined_commits += int((quar_now & (mult > 0)).sum())
            else:
                weights = submitters / submitters.sum()
                global_params = aggregate_by_worker_stacked(agg_stacks, weights)
        elif robust_on and sim.aggregation != "by_unit":
            # per-worker engines embed submissions into [W, ...] base stacks
            # and run the SAME robust pipeline; rows without a commit carry a
            # zero delta (their masked global), weight 0 and health-ineligible
            mult = _commit_multiplicity(events)
            stacks = {
                k: np.zeros((W,) + tuple(s), np.float32)
                for k, s in env.base_shapes.items()
            }
            stack_masks = {
                k: np.zeros((W,) + tuple(s), np.float32)
                for k, s in env.base_shapes.items()
            }
            for w in range(W):
                for k in stack_masks:
                    stack_masks[k][w] = coordinate_mask(
                        k, indices[w], env.unit_map, env.base_shapes
                    )
                if w in worker_params:
                    emb = embed_params(
                        worker_params[w], indices[w], env.unit_map,
                        env.base_shapes,
                    )
                    for k in stacks:
                        stacks[k][w] = emb[k]
                else:
                    for k in stacks:
                        stacks[k][w] = (
                            np.asarray(global_params[k], np.float32)
                            * stack_masks[k][w]
                        )
            global_params, rb_strikes, rb_quar, quar_now = (
                _robust_aggregate_host(
                    stacks, stack_masks, global_params, mult, events,
                    byz_cfg, ch_cfg, corrupt_on, rb_cfg,
                    sim.seed, t, rb_strikes, rb_quar,
                )
            )
            if quar_now is not None:
                quarantined_commits += int((quar_now & (mult > 0)).sum())
        else:
            submissions = [
                (worker_params[w], indices[w]) for w in active_ws if submitters[w]
            ]
            if sim.aggregation == "by_unit":
                global_params = aggregate_by_unit(
                    submissions, env.unit_map, env.base_shapes
                )
            else:
                global_params = aggregate_by_worker(
                    submissions, env.unit_map, env.base_shapes
                )
        global_params = {k: v.astype(np.float32) for k, v in global_params.items()}

        if adapt and (t % sim.prune_interval == 0 or events.drift_changed):
            _learn_rates(t, events.drift_changed)
        server_overhead += _time.perf_counter() - t0

        if t % sim.eval_every == 0:
            acc_time.append((clock, _env_accuracy(env, global_params)))

    host_roundtrips = roundtrip_total() - rt_base
    final_costs = [env.cost_for_index(indices[w]) for w in range(W)]
    return _finalize(sim, env, acc_time, het_traj, sim_traj, upd_times,
                     [retention(indices[w], env.space) for w in range(W)],
                     [extract_subparams(global_params, indices[w], env.unit_map) for w in range(W)],
                     comm_bytes, server_overhead, clock,
                     global_params=global_params, host_roundtrips=host_roundtrips,
                     scenario_rounds=scen_rows,
                     flops_per_image_final=float(np.mean([c[0] for c in final_costs])),
                     blocks_per_image_final=float(np.mean([c[2] for c in final_costs])),
                     prune_events=prune_events,
                     fault_ledger={
                         **fault_ledger(events_log),
                         "quarantined_commits": quarantined_commits,
                     })


def _scores_for(sim: SimConfig, env: _Env, worker, prune_round, params_w, index_w,
                cig_scores, state=None):
    """Importance scores in base coordinates for this worker/round.

    ``params_w`` may be None under the resident engine; the data-dependent
    criteria then extract the worker's row at this (scoring) boundary."""
    name = sim.importance
    if name == "cig_bnscalor":
        if cig_scores is None:
            raise RuntimeError("CIG order not yet frozen")
        return cig_scores
    ctx_kw = dict(unit_counts=env.space.unit_counts, worker=worker,
                  round=prune_round, seed=sim.seed)
    if name in _DATA_DEP_IMPORTANCE:
        if params_w is None:
            assert state is not None
            row = {k: np.asarray(v[worker]) for k, v in state.params.items()}
            params_w = extract_subparams(row, index_w, env.unit_map)
        x, y = env.shard_xy(worker)
        stats = local_unit_stats(env.trainer, params_w, index_w, env.space, env.unit_map, x, y)
        ctx_kw.update(weight_norms=stats["weight_norms"], grads=stats["grads"],
                      activations=stats["activations"])
    return METHODS[name](ImportanceContext(**ctx_kw))


# ---------------------------------------------------------------------------
# asynchronous methods: fedasync_s / ssp_s / dcasgd_s
# ---------------------------------------------------------------------------

def _plan_async_events(
    sim: SimConfig,
    env: _Env,
    scen: Optional[ScenarioEngine],
    participants: np.ndarray,
) -> AsyncEventPlan:
    """Pre-simulate the entire async discrete-event run (no training).

    Async workers never prune, so event timing depends only on worker
    bandwidths + jitter draws and SSP blocking only on commit counts —
    the heap loop can run to completion before any parameters exist.  This
    replays the legacy loop's exact RNG/heap order: initial ``schedule``
    per participant ascending (one jitter draw each via ``phi_from_index``;
    the per-worker path's ``env.phi(w, fetched)`` produced bit-identical
    draws because async shapes are always the base shapes), then per window
    batch: heap pops (``(time, worker)`` tuple tie-break), one
    ``make_batch_plan`` per popped row in pop order, one ``scen.rng``
    dropout draw per popped row in pop order (ONLY when dropout > 0, so
    dropout-free runs consume zero extra scenario RNG), then the per-commit
    bookkeeping walk (clock running-max, staleness before the version bump,
    SSP block/unblock with reschedule jitter draws, eval flags).

    A dropped (timed-out) commit still trains, still counts toward
    ``rounds_done``/termination, and still refetches the current global —
    but the server never merges it: no version bump, no bytes."""
    W = sim.num_workers
    method = sim.method
    idx = full_index(env.space)
    n_part = len(participants)
    drop_p = scen.cfg.dropout if scen is not None else 0.0
    # crash/recovery faults under async: one dedicated fault_rng draw per
    # popped commit in pop order (ONLY when crash is enabled, mirroring the
    # dropout stream discipline).  A crashed worker's commit still lands —
    # the crash takes it dark AFTER reporting — and its next schedule is
    # delayed by ``outage_rounds`` nominal (jitter-free) update times, so it
    # returns against a bumped server version with naturally larger
    # staleness.  No extra env.rng draws, so fault-free plans are untouched.
    crash = (
        scen.cfg.faults.crash
        if scen is not None and scen.cfg.faults is not None else None
    )
    n_crashes = 0

    fetched_ver = np.zeros(W, np.int64)
    rounds_done = np.zeros(W, np.int64)
    last_push = np.zeros(W, np.int64)
    version = 0
    push_counter = 0
    total_commits = n_part * sim.rounds
    commits = 0
    clock = 0.0
    heap: List[Tuple[float, int]] = []

    def schedule(w, now):
        nonlocal push_counter
        phi = env.phi_from_index(w, idx)
        heapq.heappush(heap, (now + phi, w))
        last_push[w] = push_counter
        push_counter += 1

    for w in participants:
        schedule(int(w), 0.0)

    workers: List[int] = []
    finishes: List[float] = []
    push_seq: List[int] = []
    staleness: List[int] = []
    versions: List[int] = []
    dropped: List[bool] = []
    refetch: List[np.ndarray] = []
    evals: List[bool] = []
    clocks: List[float] = []
    plans: List[np.ndarray] = []
    batch_starts: List[int] = [0]

    blocked: List[int] = []
    window = sim.async_window
    while commits < total_commits and heap:
        batch = [heapq.heappop(heap)]
        while (window > 0.0 and heap
               and len(batch) < total_commits - commits
               and heap[0][0] <= batch[0][0] + window):
            batch.append(heapq.heappop(heap))
        batch_plans = [
            make_batch_plan(
                len(env.shards[w]), sim.batch_size, sim.local_epochs, env.rng
            )
            for _, w in batch
        ]
        drops = (
            [bool(scen.rng.random() < drop_p) for _ in batch]
            if drop_p > 0.0 else [False] * len(batch)
        )
        crashes = (
            [bool(scen.fault_rng.random() < crash.rate) for _ in batch]
            if crash is not None else [False] * len(batch)
        )
        for (finish, w), plan, drop, crashed in zip(
            batch, batch_plans, drops, crashes
        ):
            clock = max(clock, finish)
            s = int(version - fetched_ver[w])
            if not drop:
                version += 1
            commits += 1
            rounds_done[w] += 1
            ref = np.zeros(W, bool)
            ref[w] = True
            fetched_ver[w] = version
            delay = 0.0
            if crashed:
                n_crashes += 1
                delay = crash.outage_rounds * env.phi_from_index(
                    w, idx, jitter=False
                )
            if method == "ssp_s" and rounds_done[w] >= int(
                rounds_done[participants].min()
            ) + sim.ssp_threshold:
                blocked.append(w)
            elif rounds_done[w] < sim.rounds:
                schedule(w, clock + delay)
            if method == "ssp_s" and blocked:
                min_done = int(rounds_done[participants].min())
                still = []
                for bw in blocked:
                    if (rounds_done[bw] < min_done + sim.ssp_threshold
                            and rounds_done[bw] < sim.rounds):
                        ref[bw] = True
                        fetched_ver[bw] = version
                        schedule(bw, clock)
                    else:
                        still.append(bw)
                blocked = [b for b in still if rounds_done[b] < sim.rounds]
            workers.append(int(w))
            finishes.append(float(finish))
            push_seq.append(int(last_push[w]))
            staleness.append(s)
            versions.append(version)
            dropped.append(drop)
            refetch.append(ref)
            evals.append(commits % n_part == 0)
            clocks.append(clock)
            plans.append(plan)
        batch_starts.append(commits)

    return AsyncEventPlan(
        workers=np.asarray(workers, np.int64),
        finishes=np.asarray(finishes, np.float64),
        push_seq=np.asarray(push_seq, np.int64),
        staleness=np.asarray(staleness, np.int64),
        versions=np.asarray(versions, np.int64),
        dropped=np.asarray(dropped, bool),
        refetch=(np.stack(refetch) if refetch else np.zeros((0, W), bool)),
        evals=np.asarray(evals, bool),
        clocks=np.asarray(clocks, np.float64),
        batch_starts=np.asarray(batch_starts, np.int64),
        plans=plans,
        fault_ledger=(
            dict(drift_events=0, rounds_degraded=0, rounds_skipped=0,
                 workers_recovered=n_crashes, retry_total=n_crashes)
            if crash is not None else None
        ),
    )


def _run_async(sim: SimConfig, env: _Env) -> SimResult:
    W = sim.num_workers
    lam = sim.lam
    if sim.resident_momentum:
        raise ValueError(
            "resident_momentum is a synchronous-round carry; the async "
            "schedulers restart momentum per commit like their per-worker "
            "twins"
        )

    # --- scenario: async methods honour client sampling (a static
    # C-fraction of the slot pool joins the event loop) and dropout
    # (timed-out commits in the pre-drawn event stream); churn and scripted
    # schedules stay sync-only.
    scen = ScenarioEngine(sim.scenario, W) if sim.scenario is not None else None
    if scen is not None and scen.cfg.schedule is not None:
        raise ValueError(
            "async schedulers draw their own event stream; per-round "
            "scripted schedules apply to the synchronous methods only"
        )
    if scen is not None and scen.cfg.churn > 0.0:
        raise ValueError(
            "async schedulers reject scenario churn — slot replacement "
            "resets host bookkeeping the event queue does not model; churn "
            "applies to the synchronous methods only"
        )
    if scen is not None and scen.cfg.faults is not None:
        f = scen.cfg.faults
        if f.outage is not None:
            raise ValueError(
                "async schedulers reject the outage fault family — a "
                "coordinated regional blackout is a synchronous-round "
                "concept (outage is sync-only for now); crash/recovery "
                "faults are supported under the async schedulers"
            )
        if f.drift is not None:
            raise ValueError(
                "async schedulers reject the drift fault family — "
                "capability drift exists to trigger prune-rate re-learning "
                "and async workers never prune; drift applies to the "
                "synchronous methods only"
            )
        if f.wave is not None:
            raise ValueError(
                "async schedulers reject the wave fault family — async "
                "client sampling is a static cohort drawn once at run "
                "start, not a per-round C(t); wave applies to the "
                "synchronous methods only"
            )
        if f.byzantine is not None:
            raise ValueError(
                "async schedulers reject the byzantine fault family — the "
                "compromised-cohort draw is a per-round block on the "
                "synchronous fault stream with no per-commit analogue yet "
                "(byzantine is sync-only for now)"
            )
        if f.channel is not None:
            raise ValueError(
                "async schedulers reject the channel fault family — "
                "drop/duplicate/corrupt delivery is modelled at the "
                "synchronous submission boundary, and the pre-simulated "
                "async event plan has no retry clock (channel is sync-only "
                "for now)"
            )
    rb_cfg = (
        sim.robust if sim.robust is not None and sim.robust.any_active else None
    )
    if rb_cfg is not None and rb_cfg.trim > 0.0:
        raise ValueError(
            f"RobustAggConfig.trim={rb_cfg.trim} (coordinate-wise trimmed "
            "mean) is a synchronous cohort statistic — async commits arrive "
            "one at a time with no [W, ...] stack to take order statistics "
            "over; async servers support clip + quarantine only"
        )
    participants = (
        scen.static_participants() if scen is not None else np.arange(W)
    )
    n_part = len(participants)

    # --- the whole discrete-event run, pre-simulated (commit order incl.
    # ties, staleness ints, dropout outcomes, refetch sets, clocks) — every
    # engine replays this ONE plan, so schedules are identical by
    # construction.
    plan = _plan_async_events(sim, env, scen, participants)

    if sim.engine == "fused":
        from .fused import run_async_fused   # lazy: fused imports us back

        return run_async_fused(sim, env, scen, participants, plan)

    resident = sim.engine == "masked"
    method = sim.method
    global_params = dict(env.base_params)
    idx = full_index(env.space)

    # AsyncServer.commit always rebinds a fresh params dict, so fetched
    # snapshots are safe zero-copy references on the resident path; the
    # per-worker path keeps the legacy shallow copies.
    server = AsyncServer(
        method, global_params, W, cohort_size=n_part,
        fedasync_a=sim.fedasync_a, lr=sim.lr,
        dcasgd_lambda=sim.dcasgd_lambda, dcasgd_m=sim.dcasgd_m,
        clip_norm=rb_cfg.clip if rb_cfg is not None else None,
        quarantine=rb_cfg.quarantine if rb_cfg is not None else None,
    )
    fetched = [dict(global_params) for _ in range(W)]

    state = None
    pad_steps = None
    if resident:
        shard_x, shard_y = zip(*(env.shard_xy(w) for w in range(W)))
        state = env.fleet.init_state(env.base_params, list(shard_x), list(shard_y))
        pad_steps = max(
            plan_steps(len(env.shards[w]), sim.batch_size, sim.local_epochs)
            for w in participants
        )

    comm_bytes = 0.0
    # async commits always move base-shape payloads (workers never prune)
    commit_bytes = 2.0 * sum(
        int(np.prod(s)) * 4 for s in env.base_shapes.values()
    )
    acc_time = [(0.0, _env_accuracy(env, global_params))]
    rt_base = roundtrip_total()

    for b in range(len(plan.batch_starts) - 1):
        s0, e0 = int(plan.batch_starts[b]), int(plan.batch_starts[b + 1])
        rows = [int(w) for w in plan.workers[s0:e0]]
        batch_plans = plan.plans[s0:e0]
        for p in batch_plans:  # async workers all train at the full index
            env.account_train(idx, p.shape[0])
        if resident:
            # masked scatter in: each batch worker's row becomes the global
            # snapshot it fetched at its last commit...
            env.fleet.scatter_global_rows(state, rows, [fetched[w] for w in rows])
            # ...one bucket-sized sub-stack program trains the whole batch,
            # and the trained rows come back in ONE stacked host copy.
            _, pulled = env.fleet.train_rows(
                state, rows, batch_plans, lam, pad_steps=pad_steps, to_host=True
            )
            if pulled is None:
                # no-step plans (local_epochs <= 0): commit the fetched
                # params unchanged, matching the per-worker engines
                trained_batch = [fetched[w] for w in rows]
            else:
                trained_batch = [
                    {k: v[i] for k, v in pulled.items()} for i in range(len(rows))
                ]
        else:
            jobs = []
            for w, p in zip(rows, batch_plans):
                x, y = env.shard_xy(w)
                jobs.append(FleetJob(
                    worker=w, params=fetched[w], index=idx, x=x, y=y, plan=p,
                ))
            trained_batch = env.fleet.train_all(jobs, lam)
        for i, trained in zip(range(s0, e0), trained_batch):
            w = int(plan.workers[i])
            if not plan.dropped[i]:
                global_params = server.commit(
                    w, trained, fetched[w], int(plan.staleness[i])
                )
                if not resident:
                    # per-worker path: each merged commit copies a full param
                    # dict across the host boundary — count it so
                    # host_roundtrips is honest in the baseline (SSP incl.)
                    tally_roundtrip("async_merge")
                comm_bytes += commit_bytes
            if server.version != int(plan.versions[i]):
                raise RuntimeError(
                    "async replay diverged from the pre-simulated event plan"
                )
            for rw in np.flatnonzero(plan.refetch[i]):
                fetched[int(rw)] = dict(global_params)
            if plan.evals[i]:
                acc_time.append(
                    (float(plan.clocks[i]), _env_accuracy(env, global_params))
                )

    clock = float(plan.clocks[-1]) if plan.num_events else 0.0
    host_roundtrips = roundtrip_total() - rt_base
    scen_rows = [(0, n_part, 0, 0)] if scen is not None else []
    final_cost = env.cost_for_index(idx)
    return _finalize(sim, env, acc_time, [], [], [], [1.0] * W,
                     [dict(global_params) for _ in range(W)], comm_bytes, 0.0, clock,
                     global_params=dict(global_params),
                     host_roundtrips=host_roundtrips,
                     scenario_rounds=scen_rows,
                     flops_per_image_final=final_cost[0],
                     blocks_per_image_final=final_cost[2],
                     fault_ledger={
                         **(plan.fault_ledger or {}),
                         "quarantined_commits": int(server.rejected_commits),
                     })


def _finalize(sim, env, acc_time, het_traj, sim_traj, upd_times, retentions,
              worker_params, comm_bytes, server_overhead, clock,
              global_params=None, host_roundtrips=0,
              scenario_rounds=None, flops_per_image_final=0.0,
              blocks_per_image_final=0.0, prune_events=None,
              fused_chunks=0, fault_ledger=None, stack_devices=1) -> SimResult:
    accs = np.array([a for _, a in acc_time])
    times = np.array([t for t, _ in acc_time])
    best = int(np.argmax(accs))
    param_sizes = [sum(v.size for v in p.values()) for p in worker_params]
    flops = [cnn_flops(p, sim.cnn) for p in worker_params]
    full_size = sum(v.size for v in env.base_params.values())
    if sim.mesh is not None:
        fleet_axis_size = int(sim.mesh.shape[sim.fleet_axis])
        shard_spec = f"PartitionSpec({sim.fleet_axis!r})"
    else:
        fleet_axis_size, shard_spec = 1, None
    return SimResult(
        method=sim.method,
        acc_time=acc_time,
        final_acc=float(accs[-1]),
        best_acc=float(accs[best]),
        best_acc_time=float(times[best]),
        total_time=float(clock),
        het_traj=het_traj,
        retentions=retentions,
        param_reduction=1.0 - float(np.mean(param_sizes)) / full_size,
        flops_reduction=1.0 - float(np.mean(flops)) / env.full_flops,
        comm_bytes=comm_bytes,
        server_overhead_s=server_overhead,
        recompiles=env.trainer.compile_count,
        similarity_traj=sim_traj,
        update_times=upd_times,
        engine=sim.engine,
        batched_calls=env.fleet.batched_calls,
        host_roundtrips=host_roundtrips,
        host_dispatches=env.trainer.dispatch_count,
        compile_walltime_s=env.trainer.compile_walltime_s,
        fused_chunks=fused_chunks,
        n_devices=stack_devices,
        fleet_axis_size=fleet_axis_size,
        shard_spec=shard_spec,
        prune_events=prune_events or [],
        scenario_rounds=scenario_rounds or [],
        **(fault_ledger or {}),
        bucket_sizes=sorted(env.fleet.buckets_used),
        compute=sim.compute,
        compute_interpret=(
            sim.compute == "block_skip" and env.trainer.compute_interpret
        ),
        flops_executed=env.flops_executed,
        flops_ideal=env.flops_ideal,
        blocks_executed=env.blocks_executed,
        flops_per_image_final=flops_per_image_final,
        blocks_per_image_final=blocks_per_image_final,
        global_params={k: np.asarray(v) for k, v in global_params.items()}
        if global_params is not None else None,
    )


def run_simulation(sim: SimConfig) -> SimResult:
    t0 = _time.perf_counter()
    env = _Env(sim)
    if sim.method in ("adaptcl", "fedavg", "fedavg_s"):
        if sim.engine == "fused":
            from .fused import run_sync_fused   # lazy: fused imports us back

            result = run_sync_fused(sim, env)
        else:
            result = _run_sync(sim, env)
    elif sim.method in ("fedasync_s", "ssp_s", "dcasgd_s"):
        result = _run_async(sim, env)   # routes engine == "fused" itself
    else:
        raise ValueError(f"unknown method {sim.method}")
    result.walltime_s = _time.perf_counter() - t0
    return result
