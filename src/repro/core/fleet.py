"""Fleet engine: shape-bucketed / masked vmapped local training (DESIGN §IV).

The simulator's cost model is the paper's; its *host* cost used to be W
sequential ``LocalTrainer.train`` calls per round, with one fresh jit per
distinct pruned shape — wall-clock linear in workers, recompiles linear in
pruning diversity.  This module batches the fleet:

* ``sequential`` — reference engine: one scan-train call per worker, in
  worker order.  Numerically the baseline the other engines are tested
  against.
* ``bucketed``   — workers whose sub-models share a parameter-shape
  signature (and shard/plan shapes) are stacked and trained in ONE jitted
  ``vmap``-of-``scan`` program (stacked params, stacked shards, stacked
  optimizer state, per-worker batch plans).  W homogeneous workers → one
  compile, one device program.
* ``masked``     — every worker stays at BASE shape; its sub-model is a 0/1
  coordinate mask (same masking idiom as ``kernels/pruned_matmul``: prune =
  multiply by zero, never reshape), so *all* workers bucket together and
  pruning events trigger **zero** reconfigure-recompiles (compiles happen
  only per distinct fleet-stack shape — e.g. a different number of phase-B
  pruners — never because a sub-model changed shape).  Masked training
  is numerically equivalent to reconfigured training for the CNN family
  here: a fully-masked filter produces exactly-zero activations, BN of an
  all-zero channel is ``(0)*rsqrt(eps)*0+0 = 0``, and masked-loss gradients
  vanish on pruned coordinates, so retained coordinates see the same
  function as the physically-small model.

The masked engine's *device* cost is set by the trainer's ``compute`` path:
``"dense"`` executes base-shape convs (masks are 0/1 multiplies, so pruning
saves recompiles and round-trips but zero FLOPs), while ``"block_skip"``
dispatches the convs + head through ``kernels.pruned_matmul`` — the vmapped
resident program then carries per-row block-keep flags, one fleet program
serves heterogeneous retentions, and fully-pruned mask blocks execute zero
MXU passes (device FLOPs finally track retention, the paper's speedup
story).  ``FleetEngine.compute`` surfaces which path is live.

On top of the masked idiom sits the **resident fleet state** (``FleetState``):
stacked ``[W, ...]`` base-shape param / mask / momentum arrays that live on
device across rounds.  Sub-model identity is carried ONLY by the 0/1 mask
stack — the synchronous simulator never calls ``extract_subparams`` /
``embed_params`` inside its round loop (assertable via
``aggregation.ROUNDTRIP_COUNTS``):

* ``scatter_global``  — broadcast-back is a masked scatter,
  ``P = theta_g[None] * M``;
* ``train_rounds``    — one jitted vmap-of-scan over the whole stack, with a
  per-step validity mask so ragged plans and per-round participation
  (scenario sampling / dropout) never change device shapes: the one-compile
  guarantee survives hundreds of partially-participating workers;
* ``refresh_masks``   — a pruning event only rewrites mask rows (and
  re-masks the param stack); shapes never change, so zero recompiles;
* aggregation consumes the stacks directly
  (``aggregation.aggregate_by_worker_stacked`` / ``_by_unit_stacked``).

**Participation-sized compute** (``train_rows``): when only a subset of the
slots has work this phase — scenario sampling at C < 1, straggler dropout,
or an async window batch — the active rows are gathered into a fixed-size
``[B, ...]`` sub-stack before the vmapped scan, so device FLOPs track
participation instead of W.  ``B`` is padded up to the next power of two
(capped at W, padding rows are fully step-invalid) so the whole run touches
only a logarithmic set of device shapes: recompiles are bounded by the
number of distinct sub-stack bucket sizes (``buckets_used``), and the step
dimension is padded to a per-phase constant (``worker.plan_steps`` over all
slots) so ragged subsets never add shapes of their own.  Trained rows are
scattered back into the resident stacks; the async schedulers additionally
pull the ``[B, ...]`` trained rows to host in ONE copy per fleet call (the
"stacked aggregate out" their per-commit merges consume).

Every engine consumes identical pre-drawn batch plans (``make_batch_plan``),
which is what the equivalence tests pin down.  Compiles are counted in the
underlying ``LocalTrainer.compile_count`` and surfaced as
``SimResult.recompiles``.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.optim.group_lasso import group_size_sqrt, group_size_sqrt_from_shapes

from .aggregation import (
    UnitMap,
    coordinate_mask,
    embed_params,
    extract_subparams,
    subparam_shapes,
)
from .masks import GlobalIndex, UnitFlat
from .worker import LocalTrainer, Params, stack_batch_plans

__all__ = [
    "ENGINES",
    "FleetJob",
    "FleetEngine",
    "FleetState",
    "bucket_rows",
    "global_to_shard_local",
    "gather_stack_rows",
    "scatter_stack_rows",
    "refetch_rows_jnp",
    "masks_from_presence",
    "gl_factors_from_counts",
]

# "fused" shares the masked engine's resident representation; its round loop
# additionally runs as chunked on-device lax.scan programs (core.fused)
ENGINES = ("sequential", "bucketed", "masked", "fused")


def masks_from_presence(
    presence: jnp.ndarray,                     # [W, U] flat 0/1
    flat: UnitFlat,
    unit_map: UnitMap,
    base_shapes: Mapping[str, tuple],
    broadcast: bool = False,
) -> Dict[str, jnp.ndarray]:
    """Device rebuild of the ``[W, ...]`` 0/1 mask stacks from a flat
    presence matrix — the in-scan analogue of ``FleetEngine.refresh_masks``
    (same product-over-governed-axes construction, pure ``jnp``).

    ``broadcast`` keeps each mask at size 1 along every axis no unit layer
    governs (``[W, 1, ..., n_units, ..., 1]``), which multiplies into the
    parameters as the full mask does, at the cost of the units' count."""
    W = presence.shape[0]
    rows = {
        name: presence[:, flat.offsets[l] : flat.offsets[l] + flat.sizes[l]]
        for l, name in enumerate(flat.names)
    }
    masks: Dict[str, jnp.ndarray] = {}
    for path, shape in base_shapes.items():
        m = jnp.ones((W,) + ((1,) * len(shape) if broadcast else tuple(shape)),
                     jnp.float32)
        for lname, axis in unit_map.get(path, ()):
            bshape = [W] + [1] * len(shape)
            bshape[1 + axis] = shape[axis]
            m = m * rows[lname].reshape(bshape)
        masks[path] = m
    return masks


def gl_factors_from_counts(
    counts: Mapping[str, jnp.ndarray],         # {lname: [W] retained counts}
    unit_map: UnitMap,
    base_shapes: Mapping[str, tuple],
) -> Dict[str, jnp.ndarray]:
    """Device analogue of ``group_size_sqrt_from_shapes``: per-worker
    sqrt-group-size factors from retained-unit counts alone.  A path's
    reconfigured numel is its static numel with every governed axis rescaled
    by ``count/base``; a unit layer's group size is the sum over the paths it
    governs of ``numel / count``."""
    numel: Dict[str, jnp.ndarray] = {}
    for path, shape in base_shapes.items():
        val = jnp.asarray(float(np.prod(shape)), jnp.float32)
        for lname, axis in unit_map.get(path, ()):
            val = val / float(shape[axis]) * counts[lname]
        numel[path] = val
    sizes: Dict[str, jnp.ndarray] = {}
    for path, entries in unit_map.items():
        if path not in base_shapes:
            continue
        for lname, axis in entries:
            contrib = numel[path] / jnp.maximum(counts[lname], 1.0)
            sizes[lname] = sizes.get(lname, 0.0) + contrib
    return {lname: jnp.sqrt(v) for lname, v in sizes.items()}


def bucket_rows(n: int, cap: int, multiple: int = 1) -> int:
    """Sub-stack row bucket for ``n`` active rows: the smallest power of two
    >= n, capped at the fleet size.  A handful of buckets covers every
    participation pattern, which is what bounds recompiles.

    ``multiple`` (the shard count of a mesh-sharded fleet) floors the bucket:
    a gathered sub-stack must itself divide across the fleet axis, so buckets
    below the shard count round up to it (pow2 buckets >= a pow2 shard count
    already divide; a sharded fleet's shard count is a device count, i.e.
    pow2 on every mesh we build)."""
    if n < 1:
        raise ValueError(f"bucket_rows needs n >= 1, got {n}")
    if multiple < 1:
        raise ValueError(f"bucket_rows needs multiple >= 1, got {multiple}")
    b = 1
    while b < n:
        b <<= 1
    b = min(b, cap)
    if b % multiple:
        b = min(-(-b // multiple) * multiple, cap)
        if b % multiple:
            raise ValueError(
                f"fleet size {cap} does not divide over {multiple} shards"
            )
    return b


def global_to_shard_local(
    rows: Sequence[int], num_workers: int, num_shards: int
) -> Tuple[np.ndarray, np.ndarray]:
    """Map GLOBAL worker slot ids to ``(shard, local_row)`` pairs under the
    contiguous row layout of a fleet sharded over a mesh axis: slot ``w``
    lives on shard ``w // W_local`` at local row ``w % W_local`` with
    ``W_local = W / num_shards``.  This is the index algebra behind the
    sampled-cohort gather on a sharded fleet — per-shard work is
    ``gather(local_rows[shard_ids == s])``, never a raw global ``take`` on a
    per-shard array (which would silently clamp out-of-shard rows).

    Out-of-range slot ids and non-divisible fleets raise instead of
    wrapping."""
    if num_shards < 1 or num_workers % num_shards:
        raise ValueError(
            f"fleet of {num_workers} does not divide over {num_shards} shards"
        )
    rows = np.asarray(rows, np.int64)
    if rows.size and (rows.min() < 0 or rows.max() >= num_workers):
        raise ValueError(
            f"slot ids {rows[(rows < 0) | (rows >= num_workers)]} outside "
            f"[0, {num_workers})"
        )
    w_local = num_workers // num_shards
    return rows // w_local, rows % w_local


def _check_rows(rows: np.ndarray, num_rows: Optional[int]) -> np.ndarray:
    rows = np.asarray(rows, np.int64)
    if num_rows is not None and rows.size and (
        rows.min() < 0 or rows.max() >= num_rows
    ):
        raise ValueError(
            f"row ids {rows[(rows < 0) | (rows >= num_rows)]} outside "
            f"[0, {num_rows}) — pass GLOBAL slot ids (use "
            "global_to_shard_local for per-shard layouts)"
        )
    return rows


def gather_stack_rows(
    stacks: Mapping[str, jnp.ndarray],
    rows: np.ndarray,
    num_rows: Optional[int] = None,
) -> Dict[str, jnp.ndarray]:
    """Gather rows of ``[W, ...]`` stacks into a ``[B, ...]`` sub-stack
    (``rows`` may repeat indices — bucket padding repeats row 0).

    ``rows`` are GLOBAL slot ids; pass ``num_rows=W`` to assert that (the
    device ``take`` clamps silently, so an out-of-range id would otherwise
    mis-gather).  On a mesh-sharded stack the gather is a cross-shard
    collective compiled by GSPMD — correct for any row mix."""
    idx = jnp.asarray(_check_rows(rows, num_rows))
    return {k: jnp.take(v, idx, axis=0) for k, v in stacks.items()}


def scatter_stack_rows(
    stacks: Mapping[str, jnp.ndarray],
    rows: np.ndarray,
    sub: Mapping[str, jnp.ndarray],
    num_rows: Optional[int] = None,
) -> Dict[str, jnp.ndarray]:
    """Scatter the first ``len(rows)`` rows of a sub-stack back into the
    ``[W, ...]`` stacks (the inverse of ``gather_stack_rows`` on real rows;
    bucket-padding rows beyond ``len(rows)`` are discarded).  ``rows`` are
    GLOBAL slot ids, bounds-checked like the gather."""
    idx = jnp.asarray(_check_rows(rows, num_rows))
    n = len(rows)
    return {k: v.at[idx].set(sub[k][:n]) for k, v in stacks.items()}


def refetch_rows_jnp(
    fetched: Mapping[str, jnp.ndarray],   # {path: [W, ...]} fetched snapshots
    refetch_mask: jnp.ndarray,            # [W] 0/1: rows refetching the global
    global_p: Mapping[str, jnp.ndarray],  # {path: [...]} current global
) -> Dict[str, jnp.ndarray]:
    """Masked refetch: rows flagged in ``refetch_mask`` take the current
    global, the rest keep their snapshot — the fused async engine's in-scan
    twin of ``fetched[w] = dict(global_params)`` (``refetch_mask`` is traced,
    so SSP's data-dependent unblock refetches stay inside the scan)."""
    return {
        k: jnp.where(
            refetch_mask.reshape((-1,) + (1,) * (v.ndim - 1)) > 0,
            global_p[k][None],
            v,
        )
        for k, v in fetched.items()
    }


@dataclasses.dataclass
class FleetJob:
    """One worker's local-training work item for a round phase."""

    worker: int
    params: Params            # reconfigured (physically small) sub-model
    index: GlobalIndex        # its global index I_w (base coordinates)
    x: np.ndarray             # this worker's data shard
    y: np.ndarray
    plan: np.ndarray          # [steps, batch] make_batch_plan output


class FleetEngine:
    """Dispatches a list of FleetJobs to one of the three training engines."""

    def __init__(
        self,
        trainer: LocalTrainer,
        unit_map: UnitMap,
        base_shapes: Mapping[str, tuple],
        engine: str = "sequential",
    ):
        if engine not in ENGINES:
            raise ValueError(f"unknown engine {engine!r}; choose from {ENGINES}")
        self.trainer = trainer
        self.unit_map = unit_map
        self.base_shapes = base_shapes
        self.engine = engine
        self.batched_calls = 0    # device programs launched for batched phases
        self.buckets_used: set = set()   # sub-stack row counts launched
        self._mask_cache: Dict[tuple, Params] = {}

    @property
    def compute(self) -> str:
        """Device compute path of the masked/resident programs this engine
        launches ("dense" | "block_skip") — owned by the trainer."""
        return self.trainer.compute

    # ------------------------------------------------------------------
    def train_all(self, jobs: Sequence[FleetJob], lam: float = 0.0) -> List[Params]:
        """Train every job; returns reconfigured params aligned with ``jobs``."""
        results: List[Optional[Params]] = [None] * len(jobs)
        live = [i for i, j in enumerate(jobs) if j.plan.shape[0] > 0]
        for i, j in enumerate(jobs):
            if i not in live:   # empty plan: nothing to train
                results[i] = {k: np.asarray(v) for k, v in j.params.items()}
        if not live:
            return results  # type: ignore[return-value]
        if self.engine == "sequential":
            for i in live:
                j = jobs[i]
                results[i], _ = self.trainer.train_plan(
                    j.params, self.unit_map, j.x, j.y, j.plan, lam
                )
        elif self.engine == "bucketed":
            self._run_bucketed(jobs, live, results, lam)
        else:
            self._run_masked(jobs, live, results, lam)
        return results  # type: ignore[return-value]

    # ------------------------------------------------------------------
    @staticmethod
    def _shape_sig(params: Params) -> tuple:
        return tuple(sorted((k, v.shape) for k, v in params.items()))

    def _run_bucketed(self, jobs, live, results, lam):
        buckets: Dict[tuple, List[int]] = {}
        for i in live:
            j = jobs[i]
            key = (self._shape_sig(j.params), j.x.shape, j.plan.shape)
            buckets.setdefault(key, []).append(i)
        for key, members in buckets.items():
            js = [jobs[i] for i in members]
            trained, _ = self.trainer.train_many(
                [j.params for j in js],
                self.unit_map,
                np.stack([j.x for j in js]),
                np.stack([j.y for j in js]),
                np.stack([j.plan for j in js]),
                lam,
            )
            self.batched_calls += 1
            for i, p in zip(members, trained):
                results[i] = p

    def _mask_for(self, index: GlobalIndex) -> Params:
        key = tuple(sorted((k, tuple(map(int, v))) for k, v in index.items()))
        m = self._mask_cache.get(key)
        if m is None:
            m = {
                path: coordinate_mask(path, index, self.unit_map, self.base_shapes)
                .astype(np.float32)
                for path in self.base_shapes
            }
            self._mask_cache[key] = m
        return m

    def _run_masked(self, jobs, live, results, lam):
        # all workers share the base shape -> bucket only by shard/plan shape
        buckets: Dict[tuple, List[int]] = {}
        for i in live:
            j = jobs[i]
            buckets.setdefault((j.x.shape, j.plan.shape), []).append(i)
        for key, members in buckets.items():
            js = [jobs[i] for i in members]
            embedded = [
                embed_params(j.params, j.index, self.unit_map, self.base_shapes)
                for j in js
            ]
            masks = [self._mask_for(j.index) for j in js]
            # group-lasso sqrt|g| factors from the RECONFIGURED shapes, so the
            # penalty matches the physically small model, not the base shapes
            gl_sizes = [group_size_sqrt(j.params, self.unit_map) for j in js]
            trained, _ = self.trainer.train_many(
                embedded,
                self.unit_map,
                np.stack([j.x for j in js]),
                np.stack([j.y for j in js]),
                np.stack([j.plan for j in js]),
                lam,
                masks=masks,
                gl_sizes=gl_sizes,
            )
            self.batched_calls += 1
            for i, base_p in zip(members, trained):
                # hand back the reconfigured view the rest of the pipeline uses
                results[i] = extract_subparams(base_p, jobs[i].index, self.unit_map)

    # ------------------------------------------------------------------
    # resident fleet state: [W, ...] stacks that live on device
    # ------------------------------------------------------------------

    def init_state(
        self,
        base_params: Params,
        shards_x: Sequence[np.ndarray],
        shards_y: Sequence[np.ndarray],
        sharding=None,
    ) -> "FleetState":
        """Stack W full-model replicas + their data shards on device.

        Shards are padded to the longest shard; batch plans only ever index
        below each worker's true length, so the padding is never read.

        ``sharding`` (a ``NamedSharding`` from ``specs.fleet_sharding``, or
        None for the single-device layout) places every ``[W, ...]`` stack
        row-sharded over the fleet mesh axis — the state itself is
        sharding-agnostic: nothing downstream changes shape or dtype, rows
        just live on ``num_shards`` devices as ``W = num_shards x W_local``."""
        W = len(shards_x)
        sizes = np.array([len(x) for x in shards_x], dtype=np.int64)
        n_max = int(sizes.max())
        xs = np.zeros((W, n_max) + shards_x[0].shape[1:], shards_x[0].dtype)
        ys = np.zeros((W, n_max), shards_y[0].dtype)
        for w in range(W):
            xs[w, : sizes[w]] = shards_x[w]
            ys[w, : sizes[w]] = shards_y[w]
        n_shards = 1
        if sharding is not None:
            n_shards = int(np.prod([
                sharding.mesh.shape[a]
                for a in jax.tree.leaves(tuple(sharding.spec))
            ], dtype=np.int64)) or 1
            if W % n_shards:
                raise ValueError(
                    f"fleet of {W} workers does not divide over the "
                    f"{n_shards}-way fleet mesh axis"
                )
        put = (lambda v: jax.device_put(v, sharding)) if sharding is not None \
            else jnp.asarray
        params = {
            k: put(np.broadcast_to(
                np.asarray(v)[None], (W,) + tuple(v.shape)
            ))
            for k, v in base_params.items()
        }
        # a model with per-unit (broadcast) masks starts with masks of ones
        # at size 1 on every axis: no parameter-sized stack of ones
        bcast = self.trainer.cfg.fleet_layout == "lean"
        masks = {
            k: put(np.ones((W,) + ((1,) * v.ndim if bcast else tuple(v.shape)),
                           np.float32))
            for k, v in base_params.items()
        }
        state = FleetState(
            params=params, masks=masks, momentum=None,
            xs=put(xs), ys=put(ys),
            shard_sizes=sizes, num_workers=W,
            gl_sizes={
                lname: np.full((W,), s, np.float32)
                for lname, s in group_size_sqrt_from_shapes(
                    self.base_shapes, self.unit_map
                ).items()
            },
            sharding=sharding, num_shards=n_shards,
        )
        return state

    def update_shard(self, state: "FleetState", w: int, x: np.ndarray, y: np.ndarray):
        """Swap one worker's data shard in place (scenario churn join)."""
        n_max = state.xs.shape[1]
        if len(x) > n_max:
            raise ValueError(f"churn shard ({len(x)}) exceeds resident pad ({n_max})")
        xr = np.zeros((n_max,) + x.shape[1:], x.dtype)
        yr = np.zeros((n_max,), y.dtype)
        xr[: len(x)], yr[: len(y)] = x, y
        state.xs = state.xs.at[w].set(jnp.asarray(xr))
        state.ys = state.ys.at[w].set(jnp.asarray(yr))
        state.shard_sizes[w] = len(x)

    def refresh_masks(self, state: "FleetState", indices: Sequence[GlobalIndex]):
        """Rewrite the mask stack from the workers' global indices and re-mask
        the param stack.  This is the ONLY thing a pruning event does to the
        resident state — shapes never change, so nothing recompiles."""
        W = state.num_workers
        presence: Dict[str, np.ndarray] = {}
        for lname, dim in self._unit_dims().items():
            p = np.zeros((W, dim), np.float32)
            for w in range(W):
                p[w, np.asarray(indices[w][lname], np.int64)] = 1.0
            presence[lname] = p
        for path, shape in self.base_shapes.items():
            m = np.ones((W,) + tuple(shape), np.float32)
            for lname, axis in self.unit_map.get(path, ()):
                bshape = [W] + [1] * len(shape)
                bshape[1 + axis] = shape[axis]
                m = m * presence[lname].reshape(bshape)
            state.masks[path] = jnp.asarray(m)
            state.params[path] = state.params[path] * state.masks[path]
            if state.momentum is not None:
                # cross-round momentum rows are masked like the params, so a
                # pruned unit's velocity dies with it (matching the fused
                # engine's in-scan prune)
                state.momentum[path] = state.momentum[path] * state.masks[path]
        for w in range(W):
            shapes = subparam_shapes(indices[w], self.unit_map, self.base_shapes)
            for lname, s in group_size_sqrt_from_shapes(shapes, self.unit_map).items():
                state.gl_sizes[lname][w] = s

    def _unit_dims(self) -> Dict[str, int]:
        dims: Dict[str, int] = {}
        for path, entries in self.unit_map.items():
            for lname, axis in entries:
                dims[lname] = self.base_shapes[path][axis]
        return dims

    def scatter_global(self, state: "FleetState", global_params: Params):
        """Broadcast-back (Alg. 1 server line 9) as a masked scatter:
        ``P = theta_g[None] * M`` — extract/embed never run."""
        for path, g in global_params.items():
            state.params[path] = jnp.asarray(g)[None] * state.masks[path]

    def scatter_global_rows(
        self,
        state: "FleetState",
        rows: Sequence[int],
        globals_list: Sequence[Params],
    ):
        """Masked scatter of per-row global snapshots into the resident stack:
        row ``rows[i]`` becomes ``globals_list[i] * M[rows[i]]``.

        This is the async schedulers' refetch path — each committing worker
        refetched a *different* global version, so the rows are stacked on
        host once per fleet call and written in one device op per tensor."""
        idx = jnp.asarray(np.asarray(rows, np.int64))
        for path in state.params:
            g = jnp.asarray(np.stack([gl[path] for gl in globals_list]))
            state.params[path] = state.params[path].at[idx].set(
                g * jnp.take(state.masks[path], idx, axis=0)
            )

    def stack_plans(
        self,
        plans: Sequence[Optional[np.ndarray]],
        pad_rows: Optional[int] = None,
        pad_steps: Optional[int] = None,
    ):
        """Pad per-worker batch plans into ``[R, S, batch]`` + a ``[R, S]``
        validity mask (see ``worker.stack_batch_plans``).  Returns ``None``
        when no worker has a real step this phase."""
        stacked = stack_batch_plans(plans, num_rows=pad_rows, num_steps=pad_steps)
        if stacked is None:
            return None
        stack, valid = stacked
        return jnp.asarray(stack), jnp.asarray(valid)

    def init_momentum(self, state: "FleetState"):
        """Zero the momentum stack for the cross-round resident-momentum
        mode (``SimConfig.resident_momentum``)."""
        state.momentum = {k: jnp.zeros_like(v) for k, v in state.params.items()}

    def zero_momentum_rows(self, state: "FleetState", rows: Sequence[int]):
        """Restart momentum for a set of worker rows in place.

        The one momentum-reset primitive behind both slot churn and
        crash-recovery re-entry: a returning worker refetches the global
        (the ordinary ``scatter_global`` broadcast-back) but must not reuse
        velocity accumulated against pre-crash parameters."""
        if state.momentum is None or not len(rows):
            return
        idx = jnp.asarray(np.asarray(rows, np.int64))
        state.momentum = {
            k: v.at[idx].set(0.0) for k, v in state.momentum.items()
        }

    def train_rounds(
        self,
        state: "FleetState",
        plans: Sequence[Optional[np.ndarray]],
        lam: float = 0.0,
        pad_steps: Optional[int] = None,
        carry_momentum: bool = False,
    ) -> Optional[np.ndarray]:
        """One resident device program for a whole round phase.

        Rows whose plan is ``None``/empty are not trained *and not computed*:
        when fewer than W slots have work, the active rows are gathered into
        a bucket-sized sub-stack first (``train_rows``), so device FLOPs
        track participation.  Returns per-worker mean losses aligned to the
        full slot space (idle rows report 0), or ``None`` if no worker had
        work this phase.  ``carry_momentum`` feeds ``state.momentum`` into
        the optimizer and keeps the trained stack as the next carry (the
        cross-round resident-momentum mode) instead of the default per-phase
        zero restart."""
        W = state.num_workers
        rows = [w for w, p in enumerate(plans) if p is not None and p.shape[0] > 0]
        if not rows:
            return None
        if len(rows) == W:
            stacked = self.stack_plans(plans, pad_steps=pad_steps)
            plan_stack, valid = stacked
            gl = {k: jnp.asarray(v) for k, v in state.gl_sizes.items()}
            state.params, state.momentum, losses = self.trainer.train_resident(
                state.params, state.masks, self.unit_map,
                state.xs, state.ys, plan_stack, valid, lam, gl,
                momentum_in=state.momentum if carry_momentum else None,
            )
            self.batched_calls += 1
            self.buckets_used.add(W)
            return np.asarray(losses)
        losses, _ = self.train_rows(
            state, rows, [plans[w] for w in rows], lam, pad_steps=pad_steps,
            carry_momentum=carry_momentum,
        )
        full = np.zeros(W, np.float32)
        full[rows] = losses
        return full

    def train_rows(
        self,
        state: "FleetState",
        rows: Sequence[int],
        plans: Sequence[Optional[np.ndarray]],
        lam: float = 0.0,
        pad_steps: Optional[int] = None,
        to_host: bool = False,
        carry_momentum: bool = False,
    ) -> Tuple[np.ndarray, Optional[Dict[str, np.ndarray]]]:
        """Participation-sized resident training: gather ``rows`` into a
        ``[B, ...]`` sub-stack (B = next row bucket), run ONE vmapped scan
        over it, scatter the trained rows back into the resident stacks.

        ``plans`` aligns with ``rows``.  Returns ``(losses[len(rows)],
        trained)`` where ``trained`` is a single host copy of the trained
        ``{path: [len(rows), ...]}`` rows when ``to_host`` is set (the async
        schedulers' stacked aggregate out) and ``None`` otherwise."""
        W = state.num_workers
        B = len(rows)
        bucket = bucket_rows(B, W, multiple=state.num_shards)
        rows = [int(w) for w in rows]
        rows_pad = rows + [rows[0]] * (bucket - B)
        stacked = self.stack_plans(
            list(plans) + [None] * (bucket - B),
            pad_rows=bucket, pad_steps=pad_steps,
        )
        if stacked is None:
            return np.zeros(B, np.float32), None
        plan_stack, valid = stacked
        sub_params = gather_stack_rows(state.params, rows_pad, num_rows=W)
        sub_masks = gather_stack_rows(state.masks, rows_pad, num_rows=W)
        idx = jnp.asarray(np.asarray(rows_pad, np.int64))
        xs = jnp.take(state.xs, idx, axis=0)
        ys = jnp.take(state.ys, idx, axis=0)
        gl = {
            k: jnp.asarray(np.asarray(v)[rows_pad]) for k, v in state.gl_sizes.items()
        }
        mom_in = (
            gather_stack_rows(state.momentum, rows_pad) if carry_momentum else None
        )
        out, mom_out, losses = self.trainer.train_resident(
            sub_params, sub_masks, self.unit_map, xs, ys, plan_stack, valid, lam, gl,
            momentum_in=mom_in,
        )
        self.batched_calls += 1
        self.buckets_used.add(bucket)
        state.params = scatter_stack_rows(state.params, rows, out, num_rows=W)
        if carry_momentum:
            # cross-round mode: the trained rows' velocity is the next carry
            state.momentum = scatter_stack_rows(
                state.momentum, rows, mom_out, num_rows=W
            )
        # otherwise state.momentum (a full-stack observational snapshot,
        # nothing reads it) is left untouched — momentum restarts per phase
        trained = (
            {k: np.asarray(v[:B]) for k, v in out.items()} if to_host else None
        )
        return np.asarray(losses)[:B], trained

    def params_host(self, state: "FleetState") -> Dict[str, np.ndarray]:
        """Host view of the resident param stack (submission boundary only)."""
        return {k: np.asarray(v) for k, v in state.params.items()}

    def masks_host(self, state: "FleetState") -> Dict[str, np.ndarray]:
        return {k: np.asarray(v) for k, v in state.masks.items()}


@dataclasses.dataclass
class FleetState:
    """Resident multi-worker state: everything is a ``[W, ...]`` stack.

    ``params`` rows are always masked (pruned coordinates exactly 0), so
    stacked aggregation can consume them directly; ``momentum`` is a purely
    observational snapshot of the last FULL-stack phase's optimizer state
    (momentum restarts per phase, matching the per-worker engines, and
    participation-sized sub-stack phases do not update it).  ``shard_sizes``
    records true (pre-padding) shard
    lengths; ``gl_sizes`` the per-worker sqrt-group-size factors that keep
    the group-lasso penalty equal to each physically-reconfigured twin.

    ``sharding``/``num_shards`` record the mesh placement of the stacks
    (``specs.fleet_sharding`` row-sharding over a fleet axis, or None/1 on a
    single device): the state is sharding-AGNOSTIC — shapes, dtypes and
    every consumer are identical either way, rows just live on
    ``num_shards`` devices as ``W = num_shards x W_local``."""

    params: Dict[str, jnp.ndarray]
    masks: Dict[str, jnp.ndarray]
    momentum: Optional[Dict[str, jnp.ndarray]]
    xs: jnp.ndarray
    ys: jnp.ndarray
    shard_sizes: np.ndarray
    num_workers: int
    gl_sizes: Dict[str, np.ndarray]
    sharding: Optional[object] = None
    num_shards: int = 1
