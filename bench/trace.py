"""Reduce a JAX profiler trace to device time per operation, busy time, idle
share and the longest idle gaps, each gap labelled by the host span it fell
in.

The trace is read with ``jax.profiler.ProfileData`` only; nothing here
describes a topology or loads the TPU runtime.  Device operations are the
events of the ``XLA Ops`` line of each ``/device:...`` plane.  A trace taken
on the CPU has no device plane; its operations are the host events that
carry an ``hlo_op`` stat, so the same reduction can be tested there.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

__all__ = [
    "Event", "read_events", "device_ops", "host_spans", "union_ns",
    "classify", "short_name", "module_name", "summarize", "Summary",
]

SPAN_PREFIX = "bench."


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    stats: Tuple[Tuple[str, object], ...] = ()

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    def stat(self, key: str, default=None):
        for k, v in self.stats:
            if k == key:
                return v
        return default


DEVICE_LINES = ("XLA Ops", "XLA Modules")


def read_events(trace_dir: str) -> List[Event]:
    """The events this reduction reads from the newest ``*.xplane.pb`` under
    ``trace_dir``: every host event, and the device planes' ``XLA Ops`` and
    ``XLA Modules`` lines (device events without their stats, which name
    nothing the reduction uses)."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    out: List[Event] = []
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name not in DEVICE_LINES:
                continue
            for e in line.events:
                name = e.name
                if device:
                    # keep the op's name and attributes, not its operand list
                    head, sep, rest = name.partition(" = ")
                    if sep:
                        name = head + sep + " ".join(_ATTRS.findall(rest))
                out.append(Event(plane.name, line.name, name, float(e.start_ns),
                                 float(e.duration_ns), () if device else tuple(e.stats)))
    return out


def device_ops(events: Iterable[Event], line: str = "XLA Ops") -> Dict[str, List[Event]]:
    """Device operations (or, with ``line="XLA Modules"``, program runs)
    grouped by chip (plane name)."""
    events = list(events)
    dev: Dict[str, List[Event]] = {}
    for e in events:
        if e.plane.startswith("/device:") and e.line == line and e.dur_ns > 0:
            dev.setdefault(e.plane, []).append(e)
    if dev or line != "XLA Ops":
        return dev
    for e in events:
        if e.plane.startswith("/host:") and e.stat("hlo_op") is not None and e.dur_ns > 0:
            dev.setdefault("/host:ops", []).append(e)
    return dev


def host_spans(events: Iterable[Event]) -> List[Event]:
    """The benchmark's own ``TraceAnnotation`` spans (names ``bench.*``)."""
    return [e for e in events if e.plane.startswith("/host:") and e.name.startswith(SPAN_PREFIX)]


def union_ns(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted ``(start, end)`` intervals."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


# Operation classes, matched against the instruction's own name and
# attributes (on the TPU the event's name is the HLO instruction,
# ``%fusion.7 = f32[..] fusion(%a, ..), kind=kLoop, calls=..``; its operands
# are left out, since their names say nothing of the op).  TPU traces carry
# no HLO category, and a fusion's name does not say whether it holds a
# convolution, so convolutions get no class here: training time is read per
# program from the ``XLA Modules`` line instead.  The one Mosaic kernel on
# the path is ``pruned_matmul``; on the TPU it is a ``kind=kCustom`` fusion.
CLASSES: Dict[str, Tuple[str, ...]] = {
    "pruned_matmul": ("pruned_matmul", "tpu_custom_call", "kind=kcustom"),
    "collective": ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                   "collective-permute"),
}
# Control-flow ops span the ops of their bodies; they count towards the busy
# union only, never towards an op's or a class's time.
CONTAINERS = ("while", "conditional", "call")
_ATTRS = re.compile(r'(kind=k\w+|custom_call_target="[^"]*")')


def short_name(e: Event) -> str:
    """``fusion.7`` of ``%fusion.7 = f32[...] fusion(...)``."""
    return e.name.split(" = ", 1)[0].lstrip("%")


def _text(e: Event) -> str:
    parts = [short_name(e)] + _ATTRS.findall(e.name)
    for key in ("hlo_op", "long_name", "hlo_category"):
        v = e.stat(key)
        if v is not None:
            parts.append(str(v))
    return " ".join(parts).lower()


def is_container(e: Event) -> bool:
    return short_name(e).split(".", 1)[0] in CONTAINERS


def classify(e: Event) -> Optional[str]:
    """First class whose pattern occurs in the op's own text, else None."""
    t = _text(e)
    for cls, pats in CLASSES.items():
        if any(p in t for p in pats):
            return cls
    return None


def module_name(e: Event) -> str:
    """``jit_chunk`` of the ``XLA Modules`` event ``jit_chunk(1035..)``."""
    return e.name.split("(", 1)[0]


@dataclasses.dataclass
class Summary:
    chips: int
    window_s: float
    busy_s: float                      # mean over chips of the busy union
    class_s: Dict[str, float]          # device seconds per class, mean over chips
    op_s: Dict[str, float]             # device seconds per op ("name [class]"), mean over chips
    module_s: Dict[str, float]         # device seconds per program, mean over chips
    gaps: List[Tuple[str, float]]      # longest idle gaps of the first chip

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        return sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]


def _label(spans: Sequence[Event], t: float) -> str:
    """Innermost (shortest) span covering ``t``."""
    best = None
    for s in spans:
        if s.start_ns <= t <= s.end_ns and (best is None or s.dur_ns < best.dur_ns):
            best = s
    return best.name if best is not None else "outside bench spans"


def summarize(events: Sequence[Event], window: Optional[Tuple[float, float]] = None,
              n_gaps: int = 10) -> Summary:
    """Reduce the events inside ``window`` (ns; default: the ``bench.window``
    span, else the extent of all device operations)."""
    spans = host_spans(events)
    if window is None:
        win = [s for s in spans if s.name == SPAN_PREFIX + "window"]
        if win:
            window = (win[0].start_ns, win[0].end_ns)
    dev = device_ops(events)
    if not dev:
        raise ValueError("the trace holds no device operation")
    if window is None:
        allops = [e for ops in dev.values() for e in ops]
        window = (min(e.start_ns for e in allops), max(e.end_ns for e in allops))
    w0, w1 = window
    busy, class_s, op_s = [], {}, {}
    gaps: List[Tuple[str, float]] = []
    for i, (plane, ops) in enumerate(sorted(dev.items())):
        inside = [(max(e.start_ns, w0), min(e.end_ns, w1), e) for e in ops
                  if e.end_ns > w0 and e.start_ns < w1]
        merged = union_ns((s, t) for s, t, _ in inside)
        busy.append(sum(t - s for s, t in merged))
        for s, t, e in inside:
            if is_container(e):
                continue
            cls = classify(e)
            name = short_name(e) + (f" [{cls}]" if cls else "")
            op_s[name] = op_s.get(name, 0.0) + (t - s)
            if cls is not None:
                class_s[cls] = class_s.get(cls, 0.0) + (t - s)
        if i == 0:
            edges = [w0] + [x for iv in merged for x in iv] + [w1]
            for a, b in zip(edges[0::2], edges[1::2]):
                if b > a:
                    gaps.append((_label(spans, (a + b) / 2), (b - a) * 1e-9))
    module_s: Dict[str, float] = {}
    for plane, runs in device_ops(events, "XLA Modules").items():
        for e in runs:
            d = min(e.end_ns, w1) - max(e.start_ns, w0)
            if d > 0:
                module_s[module_name(e)] = module_s.get(module_name(e), 0.0) + d
    n = len(dev)
    gaps.sort(key=lambda g: -g[1])
    return Summary(
        chips=n,
        window_s=(w1 - w0) * 1e-9,
        busy_s=sum(busy) / n * 1e-9,
        class_s={k: v / n * 1e-9 for k, v in class_s.items()},
        op_s={k: v / n * 1e-9 for k, v in op_s.items()},
        module_s={k: v / n * 1e-9 for k, v in module_s.items()},
        gaps=gaps[:n_gaps],
    )
