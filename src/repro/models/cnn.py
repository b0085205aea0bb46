"""CNNs for the faithful AdaptCL reproduction: VGG16 + ResNet bottleneck nets.

These carry real BatchNorm scaling factors — the importance signal of
CIG-BNscalor — and a filter-level prunable unit space.  Parameters are flat
``{path: array}`` dicts so `core.aggregation` / `core.masks` can slice and
embed sub-models directly (shapes are read from the arrays, so a reconfigured
smaller model runs through the same ``cnn_apply``).

Pruning protocol (paper Appendix B): VGG16 — all conv layers prunable, the
final FC is not; ResNet — the stem conv and the last conv of each residual
block (and shortcuts) are not pruned, interior convs are.

**Compute paths** (``cnn_apply(compute=...)``): ``"dense"`` runs the convs as
``lax.conv`` at whatever shapes the params carry (the masked engines pass
base-shape params with pruned coordinates zeroed — full device FLOPs).
``"block_skip"`` lowers every conv through an im2col/patches →
``[M, K] x [K, N]`` formulation onto the ``kernels.pruned_matmul`` block-skip
Pallas kernel, with per-layer 0/1 ``unit_masks`` wired along the pruning
topology (a conv's out-mask is its own unit mask; its in-mask is its
producer's, repeated over the kh*kw patch taps — the patches feature dim is
channel-major, so a pruned *prefix* of channels is a contiguous K prefix and
whole tail blocks skip).  The dense head rides the same kernel.  Device FLOPs
then track retention instead of base shape; ``cnn_block_compute`` is the
host-side proxy for exactly how many blocks/FLOPs that dispatch executes.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.masks import UnitLayer, UnitSpace
from repro.kernels.ops import pruned_matmul

__all__ = [
    "CNNConfig",
    "cnn_flops",
    "cnn_flops_from_shapes",
    "cnn_block_compute",
    "conv_mask_wiring",
    "prunable_layer_names",
    "vgg_config",
    "resnet_config",
    "VGG16_CIFAR",
    "VGG11_SMALL",
    "RESNET50_TINY",
    "RESNET20_SMALL",
    "init_cnn",
    "cnn_apply",
    "build_unit_space",
    "extract_bn_scales",
]


@dataclasses.dataclass(frozen=True)
class CNNConfig:
    name: str
    kind: str                      # "vgg" | "resnet"
    num_classes: int
    image_size: int
    # vgg: plan entries are ints (conv width) or "M" (maxpool)
    plan: Tuple = ()
    # resnet: stem width + (block_count, width) per stage
    stem: int = 64
    stages: Tuple[Tuple[int, int], ...] = ()
    bottleneck: bool = True

    # the model interface (``models.interface``): every compute path, every
    # pruning criterion, workers vmapped with masks at the parameters' shapes
    compute_paths = ("dense", "block_skip")
    importances = frozenset({
        "cig_bnscalor", "index", "no_adjacent", "no_identical", "no_constant",
        "l1", "taylor", "fpgm", "hrank",
    })
    fleet_layout = "stacked"

    def init(self, key) -> Dict[str, jnp.ndarray]:
        return init_cnn(key, self)

    def unit_space(self, params):
        return build_unit_space(self, params)

    def apply(self, params, x, **compute):
        """Logits ``[b, classes]`` (``cnn_apply``)."""
        return cnn_apply(params, self, x, **compute)

    def loss(self, logits, y):
        """Mean cross-entropy of the logits against the labels."""
        logp = jax.nn.log_softmax(logits)
        return -jnp.take_along_axis(logp, y[:, None], axis=1).mean()

    def counters(self, logits) -> dict:
        return {}

    def eval_outputs(self, params, x):
        return cnn_apply(params, self, x)

    def hits(self, logits, y) -> int:
        """Images whose largest logit is their label."""
        return int((np.argmax(np.asarray(logits), -1) == y).sum())

    def flops_from_shapes(self, shapes) -> float:
        return cnn_flops_from_shapes(shapes, self)

    def unit_scales(self, params) -> Dict[str, np.ndarray]:
        return extract_bn_scales(params, self)

    def block_unit_masks(self, masks) -> Dict[str, jnp.ndarray]:
        """Each prunable layer's unit mask, read off its ``bn_g`` mask row
        (the [width] 0/1 vector the fleet's masks hold there)."""
        return {n: masks[f"{n}/bn_g"] for n in prunable_layer_names(self)}

    def block_compute(self, unit_masks, blocks) -> Dict[str, float]:
        return cnn_block_compute(self, unit_masks, blocks)

    def default_task(self, seed: int):
        from repro.data.synthetic import SyntheticImageTask

        return SyntheticImageTask(
            num_classes=self.num_classes, image_size=self.image_size,
            train_size=1280, test_size=512, seed=seed,
        )


def vgg_config(name, plan, num_classes=10, image_size=32) -> CNNConfig:
    return CNNConfig(name=name, kind="vgg", plan=tuple(plan), num_classes=num_classes, image_size=image_size)


def resnet_config(name, stem, stages, num_classes=200, image_size=64, bottleneck=True) -> CNNConfig:
    return CNNConfig(
        name=name, kind="resnet", stem=stem, stages=tuple(stages),
        num_classes=num_classes, image_size=image_size, bottleneck=bottleneck,
    )


VGG16_CIFAR = vgg_config(
    "vgg16_cifar",
    [64, 64, "M", 128, 128, "M", 256, 256, 256, "M", 512, 512, 512, "M", 512, 512, 512, "M"],
)
# reduced same-family net for fast CPU FL simulation
VGG11_SMALL = vgg_config("vgg11_small", [16, "M", 32, "M", 64, 64, "M", 64, 64, "M"])
RESNET50_TINY = resnet_config("resnet50_tiny", 64, [(3, 64), (4, 128), (6, 256), (3, 512)])
RESNET20_SMALL = resnet_config(
    "resnet20_small", 16, [(2, 16), (2, 32), (2, 64)], num_classes=10, image_size=32, bottleneck=False
)


# ---------------------------------------------------------------------------
# init / apply
# ---------------------------------------------------------------------------

def _conv_init(key, kh, kw, cin, cout):
    fan_in = kh * kw * cin
    w = jax.random.truncated_normal(key, -2, 2, (kh, kw, cin, cout), jnp.float32)
    return w * np.sqrt(2.0 / fan_in)


def _conv_names(cfg: CNNConfig) -> List[Tuple[str, int, int, bool]]:
    """[(name, ksize, stride, prunable)] in order, for vgg plans."""
    out = []
    i = 0
    for entry in cfg.plan:
        if entry == "M":
            continue
        out.append((f"conv{i}", 3, 1, True))
        i += 1
    return out


def init_cnn(key, cfg: CNNConfig) -> Dict[str, jnp.ndarray]:
    params: Dict[str, jnp.ndarray] = {}
    keys = iter(jax.random.split(key, 256))

    def add_conv(name, kh, cin, cout):
        params[f"{name}/w"] = _conv_init(next(keys), kh, kh, cin, cout)
        params[f"{name}/bn_g"] = jnp.ones((cout,))
        params[f"{name}/bn_b"] = jnp.zeros((cout,))
        return cout

    if cfg.kind == "vgg":
        cin = 3
        i = 0
        for entry in cfg.plan:
            if entry == "M":
                continue
            cin = add_conv(f"conv{i}", 3, cin, int(entry))
            i += 1
        params["fc/w"] = (
            jax.random.truncated_normal(next(keys), -2, 2, (cin, cfg.num_classes), jnp.float32)
            * np.sqrt(1.0 / cin)
        )
        params["fc/b"] = jnp.zeros((cfg.num_classes,))
    else:  # resnet
        cin = add_conv("stem", 3, 3, cfg.stem)
        for si, (nblocks, width) in enumerate(cfg.stages):
            for bi in range(nblocks):
                pre = f"s{si}b{bi}"
                out_w = width * (4 if cfg.bottleneck else 1)
                if cfg.bottleneck:
                    add_conv(f"{pre}/c1", 1, cin, width)
                    add_conv(f"{pre}/c2", 3, width, width)
                    add_conv(f"{pre}/c3", 1, width, out_w)
                else:
                    add_conv(f"{pre}/c1", 3, cin, width)
                    add_conv(f"{pre}/c2", 3, width, out_w)
                if cin != out_w:
                    add_conv(f"{pre}/sc", 1, cin, out_w)
                cin = out_w
        params["fc/w"] = (
            jax.random.truncated_normal(next(keys), -2, 2, (cin, cfg.num_classes), jnp.float32)
            * np.sqrt(1.0 / cin)
        )
        params["fc/b"] = jnp.zeros((cfg.num_classes,))
    return params


def _conv(x, w, stride=1):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC")
    )


def _conv_block_skip(x, w, in_vec, out_vec, stride, blocks, interpret):
    """Conv as im2col patches → block-skip masked matmul.

    ``conv_general_dilated_patches`` emits the K dim channel-major
    (cin * kh * kw, spatial taps minor), so the per-channel ``in_vec`` repeats
    over kh*kw taps and a pruned channel *prefix* stays a contiguous K prefix
    — the layout that makes whole-block skipping effective under CIG/prefix
    retention."""
    kh, kw, cin, cout = w.shape
    p = jax.lax.conv_general_dilated_patches(
        x, (kh, kw), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    b, hh, ww, _ = p.shape
    wmat = jnp.transpose(w, (2, 0, 1, 3)).reshape(cin * kh * kw, cout)
    in_mask = (
        jnp.ones((cin * kh * kw,), jnp.float32) if in_vec is None
        else jnp.repeat(in_vec.astype(jnp.float32), kh * kw)
    )
    out_mask = (
        jnp.ones((cout,), jnp.float32) if out_vec is None
        else out_vec.astype(jnp.float32)
    )
    y = pruned_matmul(
        p.reshape(b * hh * ww, cin * kh * kw), wmat, in_mask, out_mask,
        block_m=blocks[0], block_n=blocks[1], block_k=blocks[2],
        interpret=interpret,
    )
    return y.reshape(b, hh, ww, cout)


def _bn(x, g, b, eps=1e-5):
    mu = x.mean(axis=(0, 1, 2))
    var = x.var(axis=(0, 1, 2))
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def conv_mask_wiring(cfg: CNNConfig) -> Dict[str, Tuple[Optional[str], Optional[str]]]:
    """conv/head name -> (input unit layer, output unit layer), ``None`` for
    an unpruned side.  This is the pruning topology ``_prunable_convs``
    encodes, viewed from each consumer: a conv's out-mask is its own unit
    layer, its in-mask is its producer's."""
    wiring: Dict[str, Tuple[Optional[str], Optional[str]]] = {}
    if cfg.kind == "vgg":
        convs = [e for e in cfg.plan if e != "M"]
        for i in range(len(convs)):
            wiring[f"conv{i}"] = (f"conv{i-1}" if i > 0 else None, f"conv{i}")
        wiring["fc"] = (f"conv{len(convs)-1}" if convs else None, None)
    else:
        wiring["stem"] = (None, None)
        for si, (nblocks, _) in enumerate(cfg.stages):
            for bi in range(nblocks):
                pre = f"s{si}b{bi}"
                if cfg.bottleneck:
                    wiring[f"{pre}/c1"] = (None, f"{pre}/c1")
                    wiring[f"{pre}/c2"] = (f"{pre}/c1", f"{pre}/c2")
                    wiring[f"{pre}/c3"] = (f"{pre}/c2", None)
                else:
                    wiring[f"{pre}/c1"] = (None, f"{pre}/c1")
                    wiring[f"{pre}/c2"] = (f"{pre}/c1", None)
                wiring[f"{pre}/sc"] = (None, None)
        wiring["fc"] = (None, None)
    return wiring


def prunable_layer_names(cfg: CNNConfig) -> Tuple[str, ...]:
    """Unit-layer names of the prunable convs, in network order."""
    return tuple(name for name, _, _ in _prunable_convs(cfg))


def cnn_apply(
    params: Dict[str, jnp.ndarray], cfg: CNNConfig, x: jnp.ndarray,
    stats: dict | None = None,
    compute: str = "dense",
    unit_masks: Optional[Dict[str, jnp.ndarray]] = None,
    blocks: Tuple[int, int, int] = (128, 128, 128),
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """x: [b, h, w, 3] -> logits [b, classes]. Shapes come from the params.

    If ``stats`` (a dict) is passed, per-conv mean|activation| per filter is
    recorded into it — the data-dependent signal for the HRank-style
    importance baseline (Fig. 2 reproduction).

    ``compute="block_skip"`` dispatches every conv (and the fc head) through
    the ``kernels.pruned_matmul`` block-skip kernel with ``unit_masks``
    ({prunable layer name: [width] 0/1}) wired along ``conv_mask_wiring`` —
    numerically the same function as the dense path on masked params (pruned
    units are exact zeros either way), but fully-pruned mask blocks execute
    zero MXU passes.  ``blocks``/``interpret`` forward to the kernel
    (``interpret=None`` auto-selects: interpreter on CPU, Mosaic on TPU).
    """
    if compute not in ("dense", "block_skip"):
        raise ValueError(f"unknown compute path {compute!r}")
    bs = compute == "block_skip"
    if bs and interpret is None:
        from repro.kernels.ops import auto_interpret

        interpret = auto_interpret()
    wiring = conv_mask_wiring(cfg) if bs else {}
    um = unit_masks or {}

    def mask_vec(lname):
        return None if lname is None else um.get(lname)

    def cbr(name, h, stride=1, relu=True):
        if bs:
            in_l, out_l = wiring[name]
            h = _conv_block_skip(
                h, params[f"{name}/w"], mask_vec(in_l), mask_vec(out_l),
                stride, blocks, interpret,
            )
        else:
            h = _conv(h, params[f"{name}/w"], stride)
        h = _bn(h, params[f"{name}/bn_g"], params[f"{name}/bn_b"])
        return jax.nn.relu(h) if relu else h

    def rec(name, h):
        if stats is not None:
            stats[name] = jnp.abs(h).mean(axis=(0, 1, 2))
        return h

    if cfg.kind == "vgg":
        i = 0
        for entry in cfg.plan:
            if entry == "M":
                x = jax.lax.reduce_window(
                    x, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID"
                )
            else:
                x = rec(f"conv{i}", cbr(f"conv{i}", x))
                i += 1
        x = x.mean(axis=(1, 2))
    else:
        x = cbr("stem", x)
        for si, (nblocks, width) in enumerate(cfg.stages):
            for bi in range(nblocks):
                pre = f"s{si}b{bi}"
                stride = 2 if (bi == 0 and si > 0) else 1
                h = rec(f"{pre}/c1", cbr(f"{pre}/c1", x, stride))
                if cfg.bottleneck:
                    h = rec(f"{pre}/c2", cbr(f"{pre}/c2", h))
                    h = cbr(f"{pre}/c3", h, relu=False)
                else:
                    h = cbr(f"{pre}/c2", h, relu=False)
                if f"{pre}/sc/w" in params:
                    x = cbr(f"{pre}/sc", x, stride, relu=False)
                elif stride != 1:
                    x = x[:, ::stride, ::stride, :]
                x = jax.nn.relu(x + h)
        x = x.mean(axis=(1, 2))
    if bs:
        in_l, _ = wiring["fc"]
        fc_in = mask_vec(in_l)
        head = pruned_matmul(
            x, params["fc/w"],
            jnp.ones((x.shape[1],), jnp.float32) if fc_in is None
            else fc_in.astype(jnp.float32),
            jnp.ones((params["fc/w"].shape[1],), jnp.float32),
            block_m=blocks[0], block_n=blocks[1], block_k=blocks[2],
            interpret=interpret,
        )
        return head + params["fc/b"]
    return x @ params["fc/w"] + params["fc/b"]


def cnn_flops(params: Dict, cfg: CNNConfig) -> float:
    """Per-image forward FLOPs of the (possibly reconfigured) model."""
    return cnn_flops_from_shapes({k: v.shape for k, v in params.items()}, cfg)


def cnn_flops_from_shapes(shapes: Dict[str, tuple], cfg: CNNConfig) -> float:
    """``cnn_flops`` from shape tuples alone (no arrays materialized) — the
    resident fleet engine's channel model derives sub-model FLOPs from the
    global index via ``core.aggregation.subparam_shapes``."""
    total = 0.0
    hw = cfg.image_size
    if cfg.kind == "vgg":
        i = 0
        for entry in cfg.plan:
            if entry == "M":
                hw //= 2
            else:
                total += 2.0 * hw * hw * int(np.prod(shapes[f"conv{i}/w"]))
                i += 1
    else:
        total += 2.0 * hw * hw * int(np.prod(shapes["stem/w"]))
        for si, (nblocks, _) in enumerate(cfg.stages):
            for bi in range(nblocks):
                if bi == 0 and si > 0:
                    hw //= 2
                pre = f"s{si}b{bi}"
                for c in ("c1", "c2", "c3", "sc"):
                    key = f"{pre}/{c}/w"
                    if key in shapes:
                        total += 2.0 * hw * hw * int(np.prod(shapes[key]))
    total += 2.0 * int(np.prod(shapes["fc/w"]))
    return total


def _base_conv_geoms(cfg: CNNConfig) -> List[Tuple[str, int, int, int, int]]:
    """[(name, ksize, cin, cout, hw)] for every conv at BASE shapes, plus the
    final ("fc", 1, cin, classes, 1) head row — the per-image matmul geometry
    the block-skip dispatch runs at."""
    out: List[Tuple[str, int, int, int, int]] = []
    hw = cfg.image_size
    if cfg.kind == "vgg":
        cin, i = 3, 0
        for entry in cfg.plan:
            if entry == "M":
                hw //= 2
            else:
                out.append((f"conv{i}", 3, cin, int(entry), hw))
                cin, i = int(entry), i + 1
    else:
        out.append(("stem", 3, 3, cfg.stem, hw))
        cin = cfg.stem
        for si, (nblocks, width) in enumerate(cfg.stages):
            for bi in range(nblocks):
                if bi == 0 and si > 0:
                    hw //= 2
                pre = f"s{si}b{bi}"
                out_w = width * (4 if cfg.bottleneck else 1)
                if cfg.bottleneck:
                    out.append((f"{pre}/c1", 1, cin, width, hw))
                    out.append((f"{pre}/c2", 3, width, width, hw))
                    out.append((f"{pre}/c3", 1, width, out_w, hw))
                else:
                    out.append((f"{pre}/c1", 3, cin, width, hw))
                    out.append((f"{pre}/c2", 3, width, out_w, hw))
                if cin != out_w:
                    out.append((f"{pre}/sc", 1, cin, out_w, hw))
                cin = out_w
    out.append(("fc", 1, cin, cfg.num_classes, 1))
    return out


def cnn_block_compute(
    cfg: CNNConfig,
    unit_masks: Dict[str, np.ndarray],
    blocks: Tuple[int, int, int] = (128, 128, 128),
) -> Dict[str, float]:
    """Host-side proxy for what the ``block_skip`` dispatch executes per
    image: ``{"flops": ..., "blocks": ..., "blocks_total": ...}``.

    ``flops`` is forward multiply-adds over the *kept* K/N blocks of every
    conv-as-matmul (and the head), ``blocks`` the executed grid-cell count
    the kernel's prefetch flags produce, ``blocks_total`` the cell count a
    never-skipping dispatch would run — their ratio is the retention-tracking
    claim the benches assert without ever touching the device."""
    from repro.kernels.pruned_matmul import matmul_executed_blocks, matmul_executed_flops

    bm, bn, bk = blocks
    wiring = conv_mask_wiring(cfg)
    flops = 0.0
    cells = 0
    cells_total = 0
    for name, ks, cin, cout, hw in _base_conv_geoms(cfg):
        in_l, out_l = wiring[name]
        in_vec = unit_masks.get(in_l) if in_l is not None else None
        out_vec = unit_masks.get(out_l) if out_l is not None else None
        in_mask = (
            np.ones(cin * ks * ks, np.float32) if in_vec is None
            else np.repeat(np.asarray(in_vec, np.float32), ks * ks)
        )
        out_mask = np.ones(cout, np.float32) if out_vec is None else np.asarray(out_vec, np.float32)
        M = hw * hw
        flops += matmul_executed_flops(M, in_mask, out_mask, block_m=bm, block_n=bn, block_k=bk)
        cells += matmul_executed_blocks(M, in_mask, out_mask, block_m=bm, block_n=bn, block_k=bk)
        cells_total += matmul_executed_blocks(
            M, np.ones_like(in_mask), np.ones_like(out_mask),
            block_m=bm, block_n=bn, block_k=bk,
        )
    return {"flops": flops, "blocks": float(cells), "blocks_total": float(cells_total)}


# ---------------------------------------------------------------------------
# prunable unit metadata
# ---------------------------------------------------------------------------

def _prunable_convs(cfg: CNNConfig) -> List[Tuple[str, int, str]]:
    """[(conv_name, width, next_consumer)] — convs whose OUTPUT filters prune."""
    out = []
    if cfg.kind == "vgg":
        convs = [e for e in cfg.plan if e != "M"]
        for i, w in enumerate(convs):
            nxt = f"conv{i+1}" if i + 1 < len(convs) else "fc"
            out.append((f"conv{i}", int(w), nxt))
    else:
        # interior convs only (paper: keep stem, block-last conv, shortcuts)
        for si, (nblocks, width) in enumerate(cfg.stages):
            for bi in range(nblocks):
                pre = f"s{si}b{bi}"
                if cfg.bottleneck:
                    out.append((f"{pre}/c1", width, f"{pre}/c2"))
                    out.append((f"{pre}/c2", width, f"{pre}/c3"))
                else:
                    out.append((f"{pre}/c1", width, f"{pre}/c2"))
    return out


def build_unit_space(cfg: CNNConfig, params) -> Tuple[UnitSpace, Dict[str, list]]:
    """Returns (UnitSpace, unit_map path->[(unit_layer, axis)])."""
    unit_map: Dict[str, list] = {}
    layers = []
    prunable = _prunable_convs(cfg)
    prunable_names = {n for n, _, _ in prunable}
    for name, width, nxt in prunable:
        w = params[f"{name}/w"]
        kh, kw, cin, cout = w.shape
        # per-filter cost: own kernel column + bn(2) + consumer input slice
        cost = kh * kw * cin + 2
        if nxt == "fc":
            cost += params["fc/w"].shape[1]
        else:
            nw = params[f"{nxt}/w"]
            cost += nw.shape[0] * nw.shape[1] * nw.shape[3]
        layers.append(UnitLayer(name=name, num_units=cout, unit_param_cost=int(cost), min_units=2))
        unit_map.setdefault(f"{name}/w", []).append((name, 3))
        unit_map.setdefault(f"{name}/bn_g", []).append((name, 0))
        unit_map.setdefault(f"{name}/bn_b", []).append((name, 0))
        if nxt == "fc":
            unit_map.setdefault("fc/w", []).append((name, 0))
        else:
            unit_map.setdefault(f"{nxt}/w", []).append((name, 2))
    total = sum(int(np.prod(v.shape)) for v in params.values())
    prunable_mass = sum(l.num_units * l.unit_param_cost for l in layers)
    space = UnitSpace(layers=tuple(layers), fixed_params=total - prunable_mass)
    return space, unit_map


def extract_bn_scales(params, cfg: CNNConfig) -> Dict[str, np.ndarray]:
    """|BN gamma| per prunable filter — the CIG-BNscalor signal (§III-D)."""
    return {
        name: np.abs(np.asarray(params[f"{name}/bn_g"], np.float64))
        for name, _, _ in _prunable_convs(cfg)
    }
