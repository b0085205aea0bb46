"""Worker-side logic (AdaptCL Alg. 1, worker part).

SparseTrain -> NetworkPrune -> NetworkReconfigure.  A worker holds a
*reconfigured* (physically small) sub-model plus its global index I_w.
Training steps are jitted per parameter-shape signature; a reconfiguration
triggers one recompilation (counted in the overhead benchmark — this is the
JAX analogue of PruneTrain's model rebuild).

Three training entry points:

* ``train`` / ``train_plan`` — one worker per call (the sequential engine);
* ``train_many`` — a *stack* of same-shaped workers trained in one jitted
  ``vmap``-of-``scan`` call (stacked params, stacked shards, stacked batch
  plans, stacked optimizer state), optionally with per-worker 0/1 parameter
  masks so heterogeneous sub-models can share the base shape (the fleet
  engine's bucketed/masked modes, see ``core.fleet``);
* ``train_resident`` — the resident fleet path: device-resident ``[W, ...]``
  base-shape stacks in, stacks out, with a per-step validity mask so ragged
  batch plans (and per-round participation) never change device shapes — an
  invalidated step leaves the carry untouched, so a worker with ``k`` valid
  steps trains exactly like a ``k``-step plan and a fully-invalid worker
  passes through unchanged.

Batch order is decoupled from the training loop via ``make_batch_plan`` so
every engine consumes the *same* minibatch sequence from the same RNG —
that is what makes the engines numerically equivalent.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.cnn import CNNConfig, cnn_apply, prunable_layer_names
from repro.optim.group_lasso import group_lasso_penalty, group_size_sqrt
from repro.optim.optimizers import apply_updates, momentum

from .masks import GlobalIndex, prune_to_budget
from .spans import count, span

__all__ = [
    "LocalTrainer",
    "PROGRAM_CACHE_SIZE",
    "clear_program_cache",
    "make_batch_plan",
    "plan_steps",
    "stack_batch_plans",
    "reslice_subparams",
    "local_unit_stats",
]

Params = Dict[str, np.ndarray]


def make_batch_plan(
    n: int, batch_size: int, epochs: float, rng: np.random.Generator
) -> np.ndarray:
    """Pre-draw the minibatch index sequence for one local training phase.

    Returns ``[steps, batch_size]`` int64 indices into the worker's shard,
    replicating ``LocalTrainer.train``'s batching exactly (fresh permutation
    per epoch, short final batch padded from the epoch's head, fractional
    epochs honoured).  ``epochs <= 0`` returns an empty ``[0, batch_size]``
    plan without consuming RNG state.
    """
    if epochs <= 0 or n <= 0:
        return np.zeros((0, batch_size), dtype=np.int64)
    total = max(1, int(round(epochs * n)))
    sels = []
    done = 0
    while done < total:
        order = rng.permutation(n)
        for i in range(0, n, batch_size):
            if done >= total:
                break
            sel = order[i : i + batch_size]
            if len(sel) < batch_size:  # keep shapes static for the jit cache
                sel = np.concatenate([sel, order[: batch_size - len(sel)]])
            sels.append(sel.astype(np.int64))
            done += batch_size
    return np.stack(sels)


def plan_steps(n: int, batch_size: int, epochs: float) -> int:
    """Number of steps ``make_batch_plan(n, batch_size, epochs, ...)`` draws,
    without consuming RNG state.

    The fleet engine uses this to pick a *constant* step pad for a whole run
    phase (the max over every worker slot), so gathered sub-stacks keep one
    plan shape no matter which subset participates — the step dimension never
    forces a recompile."""
    if epochs <= 0 or n <= 0:
        return 0
    total = max(1, int(round(epochs * n)))
    return -(-total // batch_size)


def stack_batch_plans(
    plans: Sequence[Optional[np.ndarray]],
    num_rows: Optional[int] = None,
    num_steps: Optional[int] = None,
):
    """Pad per-row batch plans into ``[R, S, batch]`` + a ``[R, S]`` validity
    mask (``None``/empty plan = fully invalid row).

    ``num_rows``/``num_steps`` pad the row and step dimensions beyond the
    given plans (padding rows/steps are invalid, so the resident trainer
    compute-and-discards them) — this is how gathered sub-stacks are bucketed
    to a small set of device shapes.  Returns ``None`` when no row has a real
    step and no explicit padding was requested."""
    steps = [0 if p is None else p.shape[0] for p in plans]
    S = max(steps) if steps else 0
    if num_steps is not None:
        S = max(S, num_steps)
    if S == 0:
        return None
    R = len(plans)
    if num_rows is not None:
        R = max(R, num_rows)
    batch = next(
        (p.shape[1] for p in plans if p is not None and p.shape[0] > 0), 1
    )
    stack = np.zeros((R, S, batch), np.int64)
    valid = np.zeros((R, S), np.float32)
    for w, p in enumerate(plans):
        if steps[w]:
            stack[w, : steps[w]] = p
            valid[w, : steps[w]] = 1.0
    return stack, valid


def reslice_subparams(
    params: Params, old_index: GlobalIndex, new_index: GlobalIndex, unit_map
) -> Params:
    """Slice a sub-model further down: new_index must nest inside old_index."""
    rel: Dict[str, np.ndarray] = {}
    for lname, old in old_index.items():
        pos = {int(u): i for i, u in enumerate(old)}
        rel[lname] = np.array([pos[int(u)] for u in new_index[lname]], dtype=np.int64)
    out: Params = {}
    for path, arr in params.items():
        for lname, axis in unit_map.get(path, ()):
            arr = np.take(arr, rel[lname], axis=axis)
        out[path] = arr
    return out


def sig_name(sig) -> str:
    """The program a jit-cache signature keys: its leading name
    (``eval_logits``, ``fused``, ``resident``, ``plan``, ...), else
    ``call``."""
    for part in sig[:2] if isinstance(sig, tuple) else ():
        if isinstance(part, tuple) and part:
            part = part[0]
        if isinstance(part, str):
            return part
    return "call"


# Built programs shared by every trainer of the process, so that a sweep's
# later simulations call the jitted functions (already traced, lowered and
# loaded) of the earlier ones.  A key is the trainer's settings and the call
# site's signature, which together hold every value a build's closure reads;
# an entry is the jitted function alone, whose closures hold configuration
# and host constants but no simulation's device arrays.  Least recently used
# entries go first.
PROGRAM_CACHE_SIZE = 16
_programs: "collections.OrderedDict[tuple, object]" = collections.OrderedDict()


def clear_program_cache() -> None:
    """Forget every built program: the next call of each signature builds,
    traces and loads it again."""
    _programs.clear()


class LocalTrainer:
    """Minibatch SGD(+momentum) with optional group-lasso sparse training.

    ``compute`` selects the masked paths' device dispatch: ``"dense"`` runs
    base-shape ``lax.conv`` programs (masks as 0/1 multiplies — full FLOPs),
    ``"block_skip"`` lowers the convs + head onto the ``kernels.pruned_matmul``
    block-skip kernel with per-worker unit masks (derived from each worker's
    ``bn_g`` mask rows), so a pruned worker's device FLOPs track its
    retention.  Only the masked/resident paths honour it — the unmasked
    engines run physically reconfigured models, which are already sized.
    ``interpret=None`` auto-selects per backend (interpreter on CPU, Mosaic
    on TPU).
    """

    def __init__(
        self,
        cnn_cfg: CNNConfig,
        lr: float = 0.05,
        beta: float = 0.9,
        compute: str = "dense",
        compute_blocks: Tuple[int, int, int] = (128, 128, 128),
        interpret: Optional[bool] = None,
    ):
        if compute not in ("dense", "block_skip"):
            raise ValueError(f"unknown compute path {compute!r}")
        self.cfg = cnn_cfg
        self.lr = lr
        self.beta = beta
        self.compute = compute
        self.compute_blocks = tuple(compute_blocks)
        if interpret is None:
            from repro.kernels.ops import auto_interpret

            interpret = auto_interpret()
        self.compute_interpret = bool(interpret)
        # what every build reads of the trainer: the first half of a key of
        # the process's table of built programs
        self._program_key = (
            cnn_cfg, float(lr), float(beta), compute, self.compute_blocks,
            self.compute_interpret,
        )
        self._prunable = prunable_layer_names(cnn_cfg)
        self._step_cache: Dict = {}
        self.compile_count = 0  # reconfigure-induced recompiles (overhead bench)
        self.dispatch_count = 0  # jitted training programs launched (host->device)

    # ---- jit-cache plumbing ----------------------------------------------

    def _call_cached(self, sig, build, *args, count_compile: bool = True):
        """Dispatch a jitted program through the signature cache.

        ``sig`` must hold every value ``build``'s closure reads beyond the
        trainer's settings: a signature new to this trainer is looked up in
        the process's table of built programs (``PROGRAM_CACHE_SIZE``
        entries), and a program found there is called as it is, warm
        (counted ``program_cache.hit`` in the spans; ``program_cache.miss``
        otherwise).  A program built here enters the table once its first
        call has completed.

        Every call counts toward ``dispatch_count`` (the per-round host
        dispatch metric ``SimResult.host_dispatches`` reports); the FIRST
        call of each signature built here runs to completion
        (``block_until_ready``) inside the span ``first_call.<name>``
        (``sig_name``), whose seconds ``SimResult.compile_walltime_s`` sums,
        so benchmarks can separate warm-up (trace + compile + one run) from
        steady-state walltime.  ``count_compile=False`` keeps a signature out
        of ``compile_count`` (``SimResult.recompiles`` means the distinct
        *training-program* signatures a simulation called, found in the
        table or not — evaluation helpers are not counted there)."""
        key = (self._program_key, sig)
        entry = self._step_cache.get(sig)
        if entry is None:
            fn = _programs.get(key)
            if fn is not None:
                _programs.move_to_end(key)
                count("program_cache.hit")
                entry = [fn, True]
            else:
                count("program_cache.miss")
                entry = [build(), False]
            self._step_cache[sig] = entry
            if count_compile:
                self.compile_count += 1
        self.dispatch_count += 1
        fn, warm = entry
        if warm:
            return fn(*args)
        with span(f"first_call.{sig_name(sig)}"):
            out = fn(*args)
            jax.block_until_ready(out)
        entry[1] = True
        _programs[key] = fn
        while len(_programs) > PROGRAM_CACHE_SIZE:
            _programs.popitem(last=False)
        return out

    def _masked_logits(self, qm, mask, xb):
        """Logits of the masked base-shape model; the block-skip path reads
        each prunable layer's unit mask off its ``bn_g`` mask row (the
        [width] 0/1 vector the fleet's ``refresh_masks`` writes)."""
        if self.compute == "block_skip":
            um = {n: mask[f"{n}/bn_g"] for n in self._prunable}
            return cnn_apply(
                qm, self.cfg, xb, compute="block_skip", unit_masks=um,
                blocks=self.compute_blocks, interpret=self.compute_interpret,
            )
        return cnn_apply(qm, self.cfg, xb)

    def _masked_ce(self, qm, mask, xb, yb):
        """Mean cross-entropy of the masked model (shared by the masked
        stacked and resident train closures)."""
        logp = jax.nn.log_softmax(self._masked_logits(qm, mask, xb))
        return -jnp.take_along_axis(logp, yb[:, None], axis=1).mean()

    def _make_loss(self, unit_map, lam: float):
        cfg = self.cfg
        frozen_map = {k: tuple(v) for k, v in unit_map.items()}

        def loss_fn(p, x, y):
            logits = cnn_apply(p, cfg, x)
            logp = jax.nn.log_softmax(logits)
            ce = -jnp.take_along_axis(logp, y[:, None], axis=1).mean()
            if lam > 0.0:
                ce = ce + group_lasso_penalty(p, frozen_map, lam)
            return ce

        return loss_fn

    def _grad_call(self, params: Params, unit_map, lam: float, *args):
        sig = self._plan_sig(params, "grad", lam)
        return self._call_cached(
            sig, lambda: jax.jit(jax.grad(self._make_loss(unit_map, lam))), *args
        )

    def train(
        self,
        params: Params,
        unit_map,
        x: np.ndarray,
        y: np.ndarray,
        epochs: float,
        batch_size: int,
        rng: np.random.Generator,
        lam: float = 0.0,
    ) -> Tuple[Params, float]:
        """Returns (new params, mean loss) — make_batch_plan + train_plan."""
        plan = make_batch_plan(len(x), batch_size, epochs, rng)
        return self.train_plan(params, unit_map, x, y, plan, lam)

    # ---- plan-based training (fleet engine paths) ------------------------

    def _make_plan_train(self, unit_map, lam: float, masked: bool):
        """scan-over-plan trainer for ONE worker; vmap-able across a stack.

        The masked variant takes the worker's 0/1 parameter mask plus its
        sqrt-group-size factors (``group_size_sqrt`` of the *reconfigured*
        sub-model) so the group-lasso penalty matches the physically small
        model exactly, not the base shapes the masked program runs at.
        """
        cfg, opt = self.cfg, momentum(self.lr, self.beta)
        frozen_map = {k: tuple(v) for k, v in unit_map.items()}

        def ce(p, xb, yb):
            logits = cnn_apply(p, cfg, xb)
            logp = jax.nn.log_softmax(logits)
            return -jnp.take_along_axis(logp, yb[:, None], axis=1).mean()

        def scan_train(loss_fn, p, x, y, plan):
            opt_state = opt.init(p)

            def body(carry, sel):
                q, st = carry
                loss, grads = jax.value_and_grad(loss_fn)(q, x[sel], y[sel])
                updates, st = opt.update(grads, st, q)
                return (apply_updates(q, updates), st), loss

            (p, _), losses = jax.lax.scan(body, (p, opt_state), plan)
            return p, jnp.mean(losses)

        if not masked:

            def train_one(p, x, y, plan):
                def loss_fn(q, xb, yb):
                    l = ce(q, xb, yb)
                    if lam > 0.0:
                        l = l + group_lasso_penalty(q, frozen_map, lam)
                    return l

                return scan_train(loss_fn, p, x, y, plan)

        else:

            def train_one(p, x, y, plan, mask, gl_size):
                def loss_fn(q, xb, yb):
                    qm = jax.tree.map(lambda w, m: w * m, q, mask)
                    l = self._masked_ce(qm, mask, xb, yb)
                    if lam > 0.0:
                        l = l + group_lasso_penalty(qm, frozen_map, lam, size_sqrt=gl_size)
                    return l

                p, loss = scan_train(loss_fn, p, x, y, plan)
                return jax.tree.map(lambda w, m: w * m, p, mask), loss

        return train_one

    def _plan_sig(self, params: Params, extra, lam: float) -> tuple:
        # lam is baked into the compiled closure, so it must key the cache
        return (tuple(sorted((k, v.shape) for k, v in params.items())), extra, float(lam))

    def train_plan(
        self, params: Params, unit_map, x: np.ndarray, y: np.ndarray,
        plan: np.ndarray, lam: float = 0.0,
    ) -> Tuple[Params, float]:
        """Train one worker through a pre-drawn ``make_batch_plan`` plan."""
        if plan.shape[0] == 0:
            return {k: np.asarray(v) for k, v in params.items()}, float("nan")
        sig = self._plan_sig(params, ("plan", x.shape, plan.shape), lam)
        p, loss = self._call_cached(
            sig,
            lambda: jax.jit(self._make_plan_train(unit_map, lam, masked=False)),
            {k: jnp.asarray(v) for k, v in params.items()},
            jnp.asarray(x), jnp.asarray(y), jnp.asarray(plan),
        )
        return {k: np.asarray(v) for k, v in p.items()}, float(loss)

    def train_many(
        self,
        params_list: Sequence[Params],
        unit_map,
        xs: np.ndarray,           # [B, n, ...] stacked shards
        ys: np.ndarray,           # [B, n]
        plans: np.ndarray,        # [B, steps, batch]
        lam: float = 0.0,
        masks: Optional[Sequence[Params]] = None,   # per-worker 0/1, same shapes
        gl_sizes: Optional[Sequence[Dict[str, float]]] = None,  # sqrt|g| per layer
    ) -> Tuple[List[Params], List[float]]:
        """Train a stack of same-shaped workers in ONE jitted vmapped call.

        All workers must share a parameter-shape signature (the fleet engine
        buckets by it); ``masks`` turns on the masked mode where heterogeneous
        sub-models ride the base shape as 0/1 unit masks, so gradients (and
        the stacked momentum state) are exactly zero on pruned coordinates.
        """
        B = len(params_list)
        assert xs.shape[0] == ys.shape[0] == plans.shape[0] == B
        stacked = {
            k: jnp.stack([jnp.asarray(p[k]) for p in params_list])
            for k in params_list[0]
        }
        masked = masks is not None
        sig = self._plan_sig(
            params_list[0], ("many", B, xs.shape[1:], plans.shape[1:], masked), lam
        )
        args = [stacked, jnp.asarray(xs), jnp.asarray(ys), jnp.asarray(plans)]
        if masked:
            args.append({
                k: jnp.stack([jnp.asarray(m[k]) for m in masks])
                for k in params_list[0]
            })
            if gl_sizes is None:  # fall back to the shapes the stack runs at
                gl_sizes = [group_size_sqrt(p, unit_map) for p in params_list]
            args.append({
                lname: jnp.asarray([s[lname] for s in gl_sizes], jnp.float32)
                for lname in gl_sizes[0]
            })
        out, losses = self._call_cached(
            sig,
            lambda: jax.jit(jax.vmap(self._make_plan_train(unit_map, lam, masked=masked))),
            *args,
        )
        return (
            [{k: np.asarray(v[i]) for k, v in out.items()} for i in range(B)],
            [float(l) for l in losses],
        )

    # ---- resident fleet path (core.fleet.FleetState) ---------------------

    def make_resident_train(self, unit_map, lam: float, carry_momentum: bool = False):
        """One base-shape masked worker with step-validity gating; vmapped
        across the whole resident ``[W, ...]`` stack by ``train_resident``
        (and embedded, un-jitted, inside the fused round engine's scan).

        Valid steps replicate the masked ``_make_plan_train`` step exactly;
        an invalid step computes-and-discards (params, momentum and loss all
        keep their carry), which is how ragged plans and non-participating
        workers share one compiled program.

        ``carry_momentum`` switches the optimizer state from the per-phase
        reset of the reference engines to a caller-supplied carry: the
        returned ``train_one`` then takes the incoming momentum stack as an
        extra leading state argument (the cross-round resident-momentum
        mode), instead of ``opt.init``-ing zeros every phase.
        """
        cfg, opt = self.cfg, momentum(self.lr, self.beta)
        frozen_map = {k: tuple(v) for k, v in unit_map.items()}

        def train_one(p, x, y, plan, valid, mask, gl_size, m0=None):
            def loss_fn(q, xb, yb):
                qm = jax.tree.map(lambda w, m: w * m, q, mask)
                l = self._masked_ce(qm, mask, xb, yb)
                if lam > 0.0:
                    l = l + group_lasso_penalty(qm, frozen_map, lam, size_sqrt=gl_size)
                return l

            opt_state = m0 if carry_momentum else opt.init(p)

            def body(carry, step):
                sel, v = step
                vb = v > 0
                q, st = carry
                with jax.named_scope("train.step"):
                    loss, grads = jax.value_and_grad(loss_fn)(q, x[sel], y[sel])
                with jax.named_scope("train.update"):
                    updates, st2 = opt.update(grads, st, q)
                    q2 = apply_updates(q, updates)
                with jax.named_scope("train.gate"):
                    q = jax.tree.map(lambda a, b: jnp.where(vb, a, b), q2, q)
                    st = jax.tree.map(lambda a, b: jnp.where(vb, a, b), st2, st)
                    return (q, st), jnp.where(vb, loss, 0.0)

            # device scopes: the op names of a profile say which phase of
            # the step an op belongs to (named scopes touch no fusion)
            with jax.named_scope("train"):
                (p, opt_state), losses = jax.lax.scan(body, (p, opt_state), (plan, valid))
                p = jax.tree.map(lambda w, m: w * m, p, mask)
                steps = jnp.maximum(valid.sum(), 1.0)
                return p, opt_state, losses.sum() / steps

        return train_one

    def train_resident(
        self,
        params_stack: Dict[str, jnp.ndarray],   # [W, ...] base-shape stacks
        masks_stack: Dict[str, jnp.ndarray],    # [W, ...] 0/1
        unit_map,
        xs: jnp.ndarray,                        # [W, n_max, ...] padded shards
        ys: jnp.ndarray,                        # [W, n_max]
        plans: jnp.ndarray,                     # [W, steps, batch]
        valid: jnp.ndarray,                     # [W, steps] 1.0 = real step
        lam: float = 0.0,
        gl_sizes: Optional[Dict[str, jnp.ndarray]] = None,   # {lname: [W]}
        momentum_in: Optional[Dict[str, jnp.ndarray]] = None,  # [W, ...] carry
    ):
        """One jitted program over the ENTIRE resident fleet stack.

        Returns (params_stack, momentum_stack, losses[W]) — all stacks stay
        jnp arrays, so nothing round-trips through the host.  When
        ``momentum_in`` is given, the optimizer state starts from that stack
        instead of zeros (cross-round resident momentum; the returned
        momentum stack is the carry for the next phase/round).
        """
        carry_m = momentum_in is not None
        shapes_sig = tuple(sorted((k, tuple(v.shape)) for k, v in params_stack.items()))
        sig = (shapes_sig, ("resident", xs.shape, plans.shape, carry_m), float(lam))

        def build():
            one = self.make_resident_train(unit_map, lam, carry_momentum=carry_m)
            if carry_m:
                def with_m(p, m0, x, y, plan, valid, mask, gl_size):
                    return one(p, x, y, plan, valid, mask, gl_size, m0)
                return jax.jit(jax.vmap(with_m))
            return jax.jit(jax.vmap(one))

        if gl_sizes is None:   # base-shape factors for every worker
            W = plans.shape[0]
            gl_sizes = {
                lname: jnp.full((W,), s, jnp.float32)
                for lname, s in group_size_sqrt(
                    {k: v[0] for k, v in params_stack.items()}, unit_map
                ).items()
            }
        if carry_m:
            return self._call_cached(
                sig, build, params_stack, momentum_in, xs, ys, plans, valid,
                masks_stack, gl_sizes,
            )
        return self._call_cached(
            sig, build, params_stack, xs, ys, plans, valid, masks_stack, gl_sizes
        )

    def gradient(self, params: Params, unit_map, x, y, lam: float = 0.0) -> Params:
        """One-batch gradient (DC-ASGD commits gradients, not models)."""
        g = self._grad_call(
            params, unit_map, lam,
            {k: jnp.asarray(v) for k, v in params.items()},
            jnp.asarray(x), jnp.asarray(y),
        )
        return {k: np.asarray(v) for k, v in g.items()}

    # ---- Alg. 1 lines 3-5: prune + reconfigure ---------------------------

    def prune_and_reconfigure(
        self,
        params: Params,
        index: GlobalIndex,
        scores: Mapping[str, np.ndarray],
        pruned_rate: float,
        space,
        unit_map,
    ) -> Tuple[Params, GlobalIndex]:
        new_index = prune_to_budget(index, scores, pruned_rate, space)
        new_params = reslice_subparams(params, index, new_index, unit_map)
        return new_params, new_index


def local_unit_stats(
    trainer: LocalTrainer,
    params: Params,
    index: GlobalIndex,
    space,
    unit_map,
    x: np.ndarray,
    y: np.ndarray,
) -> Dict[str, Dict[str, np.ndarray]]:
    """Data/sub-model-dependent importance signals, scattered to base unit
    coordinates (missing units get -inf so they sort as already-pruned).

    weight_norms -> L1/FPGM; grads -> Taylor |g.w|; activations -> HRank proxy.
    """
    from repro.optim.group_lasso import unit_group_norms

    jparams = {k: jnp.asarray(v) for k, v in params.items()}
    norms, _ = unit_group_norms(jparams, unit_map)
    grads = trainer.gradient(params, unit_map, x[:64], y[:64])
    gw = {}
    for lname in norms:
        acc = 0.0
        for path, entries in unit_map.items():
            for ln, axis in entries:
                if ln != lname:
                    continue
                g = np.asarray(grads[path], np.float64)
                w = np.asarray(params[path], np.float64)
                axes = tuple(i for i in range(g.ndim) if i != axis)
                acc = acc + np.abs((g * w).sum(axis=axes))
        gw[lname] = acc
    # activation statistic (HRank proxy): real per-filter mean|activation|
    stats: Dict[str, jnp.ndarray] = {}
    cnn_apply(jparams, trainer.cfg, jnp.asarray(x[:64]), stats=stats)
    acts = {
        lname: np.asarray(stats[lname], np.float64) for lname in norms if lname in stats
    }

    def scatter(local: np.ndarray, lname: str) -> np.ndarray:
        full = np.full(space.layer(lname).num_units, -np.inf)
        full[np.asarray(index[lname], np.int64)] = np.asarray(local, np.float64)
        return full

    return {
        "weight_norms": {k: scatter(np.asarray(v), k) for k, v in norms.items()},
        "grads": {k: scatter(v, k) for k, v in gw.items()},
        "activations": {k: scatter(v, k) for k, v in acts.items()},
    }
