"""Plain float32 reference of one synchronous AdaptCL simulation.

Written from the paper (Alg. 1 and 2, Eq. 1 and 6-7) and the simulator's
stated semantics, importing nothing of the program:

* VGG (conv-BN-ReLU, 2x2 max pools, global mean pool, fc) and basic-block
  ResNet (3x3 convs, 1x1 projection shortcuts where the width changes),
  BatchNorm on batch statistics;
* each worker trains its masked sub-model for one local epoch of minibatch
  SGD with momentum 0.9 restarted every round, cross-entropy plus the
  group-lasso term ``lam * sum_g sqrt(|g|) ||theta_g||`` over prunable units;
* the server averages the masked worker models (``1/W`` each), freezes the
  CIG order from ``|BN gamma|`` of the global model at the first learning
  event, learns each worker's pruned rate from the channel model (Alg. 2,
  Newton inverse interpolation), and the worker prunes that share of its
  parameters below a global importance threshold at the end of its next
  local epoch;
* the global model is evaluated on the test set after every round, in
  batches of 256.

Every matmul and convolution runs at ``Precision.HIGHEST``.  ``dtype =
bfloat16`` gives the control: the same computation with parameters, optimizer
state and activations in bfloat16 at the default precision.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench.data import partition_noniid
from bench.flops import convs_of, forward_flops, head_width, prunable

HIGHEST = lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# model
# ---------------------------------------------------------------------------

def init_params(model: dict, seed: int) -> Dict[str, jnp.ndarray]:
    """He-normal (truncated at 2 sigma) convs, unit BN, fc at 1/sqrt(fan_in);
    one split key per layer, in network order."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 256))
    p: Dict[str, jnp.ndarray] = {}
    for c in convs_of(model):
        shape = (c.k, c.k, c.cin, c.cout)
        p[f"{c.name}/w"] = (
            jax.random.truncated_normal(next(keys), -2, 2, shape, jnp.float32)
            * np.sqrt(2.0 / (c.k * c.k * c.cin))
        )
        p[f"{c.name}/bn_g"] = jnp.ones((c.cout,))
        p[f"{c.name}/bn_b"] = jnp.zeros((c.cout,))
    cin = head_width(model)
    p["fc/w"] = (
        jax.random.truncated_normal(next(keys), -2, 2, (cin, model["num_classes"]), jnp.float32)
        * np.sqrt(1.0 / cin)
    )
    p["fc/b"] = jnp.zeros((model["num_classes"],))
    return p


def conv(x, w, stride, precision=HIGHEST):
    """``SAME`` convolution, NHWC x HWIO, as one matmul over the k*k shifted
    (and strided) views of the zero-padded input."""
    k, _, cin, cout = w.shape
    b, hh, ww, _ = x.shape
    out = -(-hh // stride)
    pad = max((out - 1) * stride + k - hh, 0)
    lo = pad // 2
    xp = jnp.pad(x, ((0, 0), (lo, pad - lo), (lo, pad - lo), (0, 0)))
    taps = [xp[:, i:i + (out - 1) * stride + 1:stride, j:j + (out - 1) * stride + 1:stride, :]
            for i in range(k) for j in range(k)]
    cols = jnp.concatenate(taps, axis=-1).reshape(b * out * out, k * k * cin)
    y = jnp.dot(cols, w.reshape(k * k * cin, cout), precision=precision)
    return y.reshape(b, out, out, cout)


def forward(p, model: dict, x, precision=HIGHEST):
    """Logits ``[b, classes]``; BN normalises over the batch and space."""

    def cbr(name, h, stride=1, relu=True):
        h = conv(h, p[f"{name}/w"], stride, precision)
        mu = h.mean(axis=(0, 1, 2))
        var = h.var(axis=(0, 1, 2))
        h = (h - mu) * lax.rsqrt(var + 1e-5) * p[f"{name}/bn_g"] + p[f"{name}/bn_b"]
        return jnp.maximum(h, 0) if relu else h

    if model["kind"] == "vgg":
        i = 0
        for e in model["plan"]:
            if e == "M":
                x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
            else:
                x = cbr(f"conv{i}", x)
                i += 1
    else:
        x = cbr("stem", x)
        for si, (nblocks, _) in enumerate(model["stages"]):
            for bi in range(nblocks):
                pre = f"s{si}b{bi}"
                stride = 2 if (bi == 0 and si > 0) else 1
                h = cbr(f"{pre}/c2", cbr(f"{pre}/c1", x, stride), relu=False)
                if f"{pre}/sc/w" in p:
                    x = cbr(f"{pre}/sc", x, stride, relu=False)
                elif stride != 1:
                    x = x[:, ::stride, ::stride, :]
                x = jnp.maximum(x + h, 0)
    x = x.mean(axis=(1, 2))
    return jnp.dot(x, p["fc/w"], precision=precision) + p["fc/b"]


# ---------------------------------------------------------------------------
# prunable units
# ---------------------------------------------------------------------------

class Units:
    """Unit layers, their per-unit parameter cost at base shapes, and the
    arrays (with axis) each unit owns."""

    def __init__(self, model: dict, shapes: Dict[str, tuple]):
        self.model = model
        self.shapes = shapes
        self.layers: List[str] = []
        self.num: Dict[str, int] = {}
        self.cost: Dict[str, int] = {}
        self.owns: Dict[str, List[Tuple[str, int]]] = {}
        for name, consumer in prunable(model):
            kh, kw, cin, cout = shapes[f"{name}/w"]
            cost = kh * kw * cin + 2
            if consumer == "fc":
                cost += shapes["fc/w"][1]
                cons = ("fc/w", 0)
            else:
                nw = shapes[f"{consumer}/w"]
                cost += nw[0] * nw[1] * nw[3]
                cons = (f"{consumer}/w", 2)
            self.layers.append(name)
            self.num[name] = cout
            self.cost[name] = int(cost)
            self.owns[name] = [(f"{name}/w", 3), (f"{name}/bn_g", 0),
                               (f"{name}/bn_b", 0), cons]
        self.total = sum(int(np.prod(s)) for s in shapes.values())
        self.fixed = self.total - sum(self.num[l] * self.cost[l] for l in self.layers)

    def full(self) -> Dict[str, np.ndarray]:
        return {l: np.arange(self.num[l]) for l in self.layers}

    def retained_params(self, index) -> int:
        return self.fixed + sum(len(index[l]) * self.cost[l] for l in self.layers)

    def retention(self, index) -> float:
        return self.retained_params(index) / self.total

    def sub_shapes(self, index) -> Dict[str, tuple]:
        out = {}
        for path, shape in self.shapes.items():
            s = list(shape)
            for l in self.layers:
                for p, ax in self.owns[l]:
                    if p == path:
                        s[ax] = len(index[l])
            out[path] = tuple(s)
        return out

    def param_mask(self, index) -> Dict[str, np.ndarray]:
        m = {k: np.ones(s, np.float32) for k, s in self.shapes.items()}
        for l in self.layers:
            v = np.zeros(self.num[l], np.float32)
            v[index[l]] = 1.0
            for p, ax in self.owns[l]:
                b = [1] * len(self.shapes[p])
                b[ax] = self.num[l]
                m[p] = m[p] * v.reshape(b)
        return m

    def group_sqrt_sizes(self, index) -> np.ndarray:
        """sqrt(|g|) per unit layer at the worker's retained shapes."""
        sub = self.sub_shapes(index)
        out = []
        for l in self.layers:
            n = 0
            for p, ax in self.owns[l]:
                n += int(np.prod(sub[p])) // int(sub[p][ax])
            out.append(np.sqrt(n))
        return np.asarray(out, np.float32)

    def prune(self, index, scores, rate: float):
        """Remove the lowest-scored retained units, across all layers, until
        ``rate`` of the current parameters is gone; at least 2 units stay
        in every layer; ties break on (layer name, unit)."""
        if rate == 0.0:
            return {k: v.copy() for k, v in index.items()}
        budget = rate * self.retained_params(index)
        entries = sorted(
            (float(scores[l][u]), l, int(u)) for l in self.layers for u in index[l]
        )
        left = {l: len(index[l]) for l in self.layers}
        gone = {l: set() for l in self.layers}
        removed = 0
        for _, l, u in entries:
            if removed >= budget:
                break
            if left[l] <= 2:
                continue
            gone[l].add(u)
            left[l] -= 1
            removed += self.cost[l]
        return {l: np.array([u for u in index[l] if int(u) not in gone[l]], np.int64)
                for l in self.layers}


# ---------------------------------------------------------------------------
# Alg. 2 and the channel model
# ---------------------------------------------------------------------------

def bandwidths(W: int, sigma: float, model_bytes: float, t_train: float,
               comm_ratio: float = 3.0) -> List[float]:
    """Eq. 6-7: update times spread uniformly from the fastest (last) worker
    to ``sigma`` times it; the fastest link carries ``comm_ratio x t_train``."""
    bmax = 2.0 * model_bytes / (comm_ratio * t_train)
    phi_fast = 2.0 * model_bytes / bmax + t_train
    if W == 1:
        return [bmax]
    return [2.0 * model_bytes / (phi_fast * (1.0 + (sigma - 1.0) / (W - 1) * (W - w)) - t_train)
            for w in range(1, W + 1)]


def _inverse_gamma(gammas: Sequence[float], phis: Sequence[float], target: float) -> float:
    """gamma at ``target`` by Newton interpolation of gamma over phi, on the
    last 8 checkpoints with repeated phi nodes collapsed to the latest."""
    pts = {}
    for p, g in zip(phis[-8:], gammas[-8:]):
        pts[round(float(p), 9)] = (float(p), float(g))
    xs, ys = zip(*sorted(pts.values()))
    if len(xs) == 1:
        return ys[0] * target / xs[0]
    xs = np.asarray(xs, np.float64)
    c = np.asarray(ys, np.float64).copy()
    for j in range(1, len(xs)):
        c[j:] = (c[j:] - c[j - 1:-1]) / (xs[j:] - xs[:-j])
    acc = c[-1]
    for k in range(len(c) - 2, -1, -1):
        acc = acc * (target - xs[k]) + c[k]
    return float(acc)


def learn_rates(hist: List[Tuple[List[float], List[float]]],
                gammas: Sequence[float], phis: Sequence[float]) -> List[float]:
    """Alg. 2 with rho_max 0.5, rho_min 0.02, gamma_min 0.1, alpha 2."""
    phi_min = float(min(phis))
    rates = []
    for w, (hg, hp) in enumerate(hist):
        g, ph = float(gammas[w]), float(phis[w])
        if len({round(x, 12) for x in hg}) >= 2:
            tgt = min(max(_inverse_gamma(hg, hp, phi_min), 0.1), g)
            if g - tgt < 0.02:
                tgt = g
            r = (g - tgt) / g
        else:
            r = (ph - phi_min) / (2.0 * ph)
        r = float(np.clip(r, 0.0, 0.5))
        if g * (1.0 - r) < 0.1:
            r = max(0.0, 1.0 - 0.1 / g)
        rates.append(0.0 if r < 0.02 else r)
    return rates


def batch_plan(n: int, batch: int, epochs: float, rng: np.random.Generator) -> np.ndarray:
    """``[steps, batch]`` shard indices: a fresh permutation per epoch, the
    short last batch filled from the head of that permutation."""
    if epochs <= 0 or n <= 0:
        return np.zeros((0, batch), np.int64)
    total = max(1, int(round(epochs * n)))
    sels, done = [], 0
    while done < total:
        order = rng.permutation(n)
        for i in range(0, n, batch):
            if done >= total:
                break
            sel = order[i:i + batch]
            if len(sel) < batch:
                sel = np.concatenate([sel, order[: batch - len(sel)]])
            sels.append(sel)
            done += batch
    return np.stack(sels).astype(np.int64)


# ---------------------------------------------------------------------------
# simulation
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Outcome:
    """What a simulation produced, in the terms the comparison reads."""
    globals: Dict[int, Dict[str, np.ndarray]]          # global model after round t (0 = init)
    accs: List[float]                                  # test accuracy after rounds 0..
    retentions: List[float]                            # final, per worker
    prune_events: Dict[Tuple[int, int], Dict[str, np.ndarray]]
    first_grad_norms: Optional[Dict[str, float]] = None


def _local_train_fn(model, lam, lr, layers, owns, dtype, precision, fault):
    """One worker's local phase: minibatch SGD with momentum from zero over
    its plan, on the masked parameters; invalid (padding) steps leave the
    state as it was."""

    def penalty(q, gl):
        total = jnp.zeros((), dtype)
        for i, l in enumerate(layers):
            sq = 0.0
            for path, ax in owns[l]:
                a = q[path]
                sq = sq + jnp.sum(jnp.square(a), axis=tuple(d for d in range(a.ndim) if d != ax))
            total = total + gl[i] * jnp.sum(jnp.sqrt(jnp.maximum(sq, 1e-12)))
        return lam * total

    def loss_fn(q, mask, gl, xb, yb):
        qm = jax.tree.map(lambda a, m: a * m, q, mask)
        if fault == "half_batch":
            xb, yb = xb[: xb.shape[0] // 2], yb[: yb.shape[0] // 2]
        logp = jax.nn.log_softmax(forward(qm, model, xb, precision))
        ce = -jnp.take_along_axis(logp, yb[:, None], axis=1).mean()
        return ce + penalty(qm, gl) if lam > 0 else ce

    def one(p, mask, gl, x, y, plan, valid):
        def body(carry, inp):
            q, v = carry
            sel, ok = inp
            g = jax.grad(loss_fn)(q, mask, gl, x[sel], y[sel])
            v2 = jax.tree.map(lambda a, b: (0.9 * a + b).astype(dtype), v, g)
            q2 = jax.tree.map(lambda a, b: (a - lr * b).astype(dtype), q, v2)
            if fault == "unchanged":
                q2, v2 = q, v
            keep = lambda new, old: jnp.where(ok > 0, new, old)
            return (jax.tree.map(keep, q2, q), jax.tree.map(keep, v2, v)), None

        v0 = jax.tree.map(jnp.zeros_like, p)
        (p, _), _ = lax.scan(body, (p, v0), (plan, valid))
        return jax.tree.map(lambda a, m: a * m, p, mask)

    return jax.jit(one), jax.jit(jax.grad(loss_fn))


def simulate(cfg: dict, wl: dict, task, seed: int, dtype=jnp.float32,
             precision=HIGHEST, fault: Optional[str] = None,
             train_rounds: Optional[int] = None, log=None) -> Outcome:
    """One simulation of the cell (config ``cfg``, workload ``wl``) on the
    benchmark's ``task`` with the program-side seed ``seed``.

    The workers train in the first ``train_rounds`` rounds (default: all).
    After those, only the server's schedule goes on (batch plans drawn, the
    channel model, Alg. 2 and the index-order prunes), which needs no model
    where the importance order does not read it."""
    model = cfg["model"]
    W, batch, E = cfg["num_workers"], cfg["batch_size"], cfg["local_epochs"]
    lr, lam, PI = cfg["lr"], cfg["lam"], cfg["prune_interval"]
    T = cfg["rounds"]
    K = T if train_rounds is None else train_rounds
    beta = wl.get("beta", 1.0)
    adapt = wl["method"] == "adaptcl"
    importance = wl.get("importance", "cig_bnscalor")

    p0 = init_params(model, seed)
    shapes = {k: tuple(v.shape) for k, v in p0.items()}
    units = Units(model, shapes)
    layers = units.layers
    shards = partition_noniid(task.y_train, W, cfg["noniid_s"], seed)
    n_w = [len(s) for s in shards]
    xs = [jnp.asarray(task.x_train[sh], dtype) for sh in shards]
    ys = [jnp.asarray(task.y_train[sh]) for sh in shards]

    full_bytes = units.total * 4 + 4 * sum(units.num.values()) + 8
    full_flops = forward_flops(model)
    bws = bandwidths(W, wl.get("sigma", 2.0), full_bytes, 1.0)
    rng = np.random.default_rng(seed + 17)
    train, grad0 = _local_train_fn(model, lam, lr, layers, units.owns, dtype, precision, fault)

    test_x = jnp.asarray(task.x_test, dtype)
    fwd = jax.jit(lambda q, xb: forward(q, model, xb, precision))

    def accuracy(g):
        gq = {k: v.astype(dtype) for k, v in g.items()}
        hits = 0
        for i in range(0, len(task.y_test), 256):
            lg = np.asarray(fwd(gq, test_x[i:i + 256]).astype(jnp.float32))
            hits += int((lg.argmax(-1) == task.y_test[i:i + 256]).sum())
        return hits / len(task.y_test)

    def phi(w, index, jitter=True):
        sub = units.sub_shapes(index)
        nbytes = sum(int(np.prod(s)) * 4 for s in sub.values())
        kept = {l: len(index[l]) for l in layers}
        rel = forward_flops(model, kept) / full_flops
        t = 2.0 * nbytes / bws[w] + (0.9 + 0.1 * rel) * E
        j = float(np.exp(rng.normal(0, 0.02))) if jitter else 1.0
        return t * (j * 1.0)

    if K < T and importance != "index":
        raise ValueError("the schedule runs on without training only for index importance")
    glob = {k: np.asarray(v, np.float32) for k, v in p0.items()}
    globs = {0: glob}
    indices = [units.full() for _ in range(W)]
    hist: List[Tuple[List[float], List[float]]] = [([], []) for _ in range(W)]
    pending = [0.0] * W
    interval: List[List[float]] = [[] for _ in range(W)]
    cig = None
    prune_events: Dict[Tuple[int, int], Dict[str, np.ndarray]] = {}
    accs = [accuracy(glob)]
    first_grads = None

    def learn(t):
        """Alg. 2 at a learning event: every ``PI`` rounds."""
        nonlocal cig, pending, interval
        if not (adapt and t % PI == 0):
            return
        if cig is None and importance == "cig_bnscalor":
            cig = {l: np.abs(np.asarray(glob[f"{l}/bn_g"], np.float64)) for l in layers}
        gam = [units.retention(indices[w]) for w in range(W)]
        phis = [float(np.mean(interval[w])) if interval[w]
                else phi(w, indices[w], jitter=False) for w in range(W)]
        for w in range(W):
            hist[w][0].append(gam[w])
            hist[w][1].append(phis[w])
        pending = learn_rates(hist, gam, phis)
        interval = [[] for _ in range(W)]

    def scores():
        """Unit importance: CIG-BNscalor's |gamma| of the global model frozen
        at the first learning event, or the index order (higher unit ids
        pruned first)."""
        if importance == "index":
            return {l: -np.arange(units.num[l], dtype=np.float64) for l in layers}
        return cig

    def run_phase(plans, params, idx):
        steps = max(p.shape[0] for p in plans)
        if steps == 0:
            return params
        out = []
        for w, pl in enumerate(plans):
            plan = np.zeros((steps, batch), np.int64)
            valid = np.zeros((steps,), np.float32)
            plan[: pl.shape[0]] = pl
            valid[: pl.shape[0]] = 1.0
            mask = {k: jnp.asarray(v, dtype) for k, v in units.param_mask(idx[w]).items()}
            gl = jnp.asarray(units.group_sqrt_sizes(idx[w]), dtype)
            out.append(train(params[w], mask, gl, xs[w], ys[w], jnp.asarray(plan),
                             jnp.asarray(valid)))
        return out

    for t in range(1, T + 1):
        t_round = time.perf_counter()
        plans_a, plans_b, prune_now = [], [], []
        for w in range(W):
            if adapt and pending[w] > 0.0:
                e1, e2 = beta * E, (1 - beta) * E
                prune_now.append(True)
            else:
                e1, e2 = E, 0.0
                prune_now.append(False)
            plans_a.append(batch_plan(n_w[w], batch, e1, rng))
            plans_b.append(batch_plan(n_w[w], batch, e2, rng))
        if t > K:
            for w in range(W):
                if prune_now[w]:
                    indices[w] = units.prune(indices[w], scores(), pending[w])
                    prune_events[(t, w)] = indices[w]
                interval[w].append(phi(w, indices[w]))
                pending[w] = 0.0
            learn(t)
            continue
        params = []
        for w in range(W):
            m = units.param_mask(indices[w])
            params.append({k: jnp.asarray(glob[k] * m[k], dtype) for k in shapes})
        if first_grads is None:
            m = {k: jnp.asarray(v, dtype) for k, v in units.param_mask(indices[0]).items()}
            sel = plans_a[0][0]
            g = grad0(params[0], m, jnp.asarray(units.group_sqrt_sizes(indices[0]), dtype),
                      xs[0][sel], ys[0][sel])
            first_grads = {k: float(jnp.linalg.norm(v.astype(jnp.float32))) for k, v in g.items()}
        params = run_phase(plans_a, params, indices)
        if any(prune_now):
            for w in range(W):
                if prune_now[w]:
                    indices[w] = units.prune(indices[w], scores(), pending[w])
                    prune_events[(t, w)] = indices[w]
                    m = units.param_mask(indices[w])
                    params[w] = {k: v * jnp.asarray(m[k], dtype) for k, v in params[w].items()}
            params = run_phase(
                [plans_b[w] if prune_now[w] else np.zeros((0, batch), np.int64)
                 for w in range(W)],
                params, indices,
            )
        for w in range(W):
            interval[w].append(phi(w, indices[w]))
            pending[w] = 0.0
        glob = {k: np.asarray(sum(p[k].astype(jnp.float32) for p in params) / W)
                for k in shapes}
        globs[t] = glob
        learn(t)
        accs.append(accuracy(glob))
        if log is not None:
            log(f"reference round {t}: {time.perf_counter() - t_round:.3f} s, acc {accs[-1]}")

    return Outcome(
        globals=globs, accs=accs,
        retentions=[units.retention(i) for i in indices],
        prune_events=prune_events, first_grad_norms=first_grads,
    )
