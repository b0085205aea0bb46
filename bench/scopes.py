"""Device time per named scope and idle time per program span, from a JAX
profiler trace of the simulator.

The program names its own work (``repro.core.spans``): host spans
``adaptcl.<name>`` (``sim``, ``setup``, ``chunk.plan``, ``chunk.dispatch``,
``chunk.readback``, ``chunk.account``, ``evaluate``, ``alg2``,
``first_call.<program>``) and device scopes in the training program's op
names (``jax.named_scope``: ``broadcast``, ``train`` with ``train.step`` /
``train.update`` / ``train.gate``, ``importance``, ``prune``, ``dgc``,
``aggregate``).  This module adds two views to ``bench/trace.py``'s
reduction, whose outputs it leaves as they are:

* ``scope_s``: device seconds of the training program's operations (runs of
  ``jit_chunk``) per innermost known scope, mean over chips;
* ``idle_by_span``: every idle interval of the first chip, cut at span
  edges and summed by the innermost ``adaptcl.*`` span (a ``bench.*``
  harness span where no program span is open), with no top-N cut.

A device operation's scope is read from its op name, the ``op_name``
metadata of the instruction of that name in the compiled program's HLO text
(``op_names_from_hlo``): neither a TPU trace (its operations carry only
their device offset and duration) nor a CPU trace names it.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from bench import trace as tr

__all__ = [
    "SCOPES", "ScopedEvent", "read_events", "op_names_from_hlo", "scope_of",
    "scope_s", "span_segments", "idle_by_span", "idle_under",
]

# innermost wins; ``train.*`` lie inside ``train``
SCOPES = ("broadcast", "train", "train.step", "train.update", "train.gate",
          "importance", "prune", "dgc", "aggregate")
PROGRAM_PREFIX = "adaptcl."
HARNESS_PREFIX = tr.SPAN_PREFIX
_WRAPPED = re.compile(r"^[\w.]+\((.*)\)$")
_HLO_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (.*)$", re.M)
_HLO_OP_NAME = re.compile(r'metadata=\{[^}]*op_name="([^"]*)"')
_HLO_REF = re.compile(r"%([\w.\-]+)")
_OPCODE = re.compile(r"\s([a-z][a-z0-9-]*)\(")
# control flow spans the operations of its bodies (``trace.CONTAINERS`` goes
# by name, and XLA may name a conditional ``cond.N``)
CONTAINER_OPCODES = ("while", "conditional", "call")


@dataclasses.dataclass(frozen=True)
class ScopedEvent(tr.Event):
    """A trace event with, for device operations, the program it ran in and
    the op name (``jit(chunk)/while/body/.../train/...``) it came from."""
    module: str = ""
    op_name: str = ""
    opcode: str = ""


def op_names_from_hlo(text: str) -> Dict[str, str]:
    """``{instruction: op_name}`` of an HLO module's text
    (``Compiled.as_text()``).  An instruction the compiler added without
    metadata (an asynchronous copy, the reverse of a convolution's kernel)
    takes the op name of its first user that has one."""
    names: Dict[str, str] = {}
    uses: List[Tuple[str, List[str]]] = []
    for m in _HLO_INSTR.finditer(text):
        op = _HLO_OP_NAME.search(m.group(2))
        if op:
            names[m.group(1)] = op.group(1)
        uses.append((m.group(1), _HLO_REF.findall(m.group(2))))
    instructions = {name for name, _ in uses}
    changed = True
    while changed:
        changed = False
        for user, used in uses:
            for u in used if user in names else ():
                if u in instructions and u not in names:
                    names[u] = names[user]
                    changed = True
    return names


def opcode_of(name: str) -> str:
    """``conditional`` of the trace name ``%cond.3 = (f32[2]) conditional(...)``."""
    m = _OPCODE.search(name.partition(" = ")[2])
    return m.group(1) if m else ""


def _bare(component: str) -> str:
    """``train`` of ``vmap(train)``, ``transpose(jvp(step))`` -> ``step``."""
    m = _WRAPPED.match(component)
    while m:
        component = m.group(1)
        m = _WRAPPED.match(component)
    return component


def scope_of(op_name: str) -> Optional[str]:
    """The innermost known scope on an op name's path, else None."""
    found = None
    for part in op_name.split("/"):
        part = _bare(part)
        if part in SCOPES:
            found = part
    return found


def read_events(trace_dir: str, op_names: Optional[Dict[str, Dict[str, str]]] = None
                ) -> List[ScopedEvent]:
    """``trace.read_events`` of the newest ``*.xplane.pb`` under
    ``trace_dir``, with each device operation's program (its ``hlo_module``
    stat, else the ``XLA Modules`` run it started in) and op name
    (``op_names[program][instruction]``)."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    op_names = op_names or {}
    out: List[ScopedEvent] = []
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        runs: List[Tuple[float, float, str]] = []
        for line in sorted(plane.lines, key=lambda l: l.name != "XLA Modules"):
            if device and line.name not in tr.DEVICE_LINES:
                continue
            runs.sort()
            for e in line.events:
                start, dur = float(e.start_ns), float(e.duration_ns)
                stats = dict(e.stats)
                if device and line.name == "XLA Modules":
                    runs.append((start, start + dur, e.name.split("(", 1)[0]))
                name, module, op_name, opcode = e.name, "", "", opcode_of(e.name)
                if device:
                    head, sep, rest = name.partition(" = ")
                    if sep:
                        name = head + sep + " ".join(tr._ATTRS.findall(rest))
                if device and line.name == "XLA Ops" or "hlo_op" in stats:
                    module = str(stats.get("hlo_module", "")) or _run_at(runs, start)
                    short = str(stats.get("hlo_op", "")) or name.split(" = ", 1)[0].lstrip("%")
                    op_name = op_names.get(module, {}).get(short, "")
                out.append(ScopedEvent(plane.name, line.name, name, start, dur,
                                       () if device else tuple(stats.items()),
                                       module=module, op_name=op_name, opcode=opcode))
    return out


def _run_at(runs: Sequence[Tuple[float, float, str]], t: float) -> str:
    """The program of the run (sorted by start) in progress at ``t``."""
    i = bisect.bisect_right(runs, (t, float("inf"), "")) - 1
    return runs[i][2] if i >= 0 and t < runs[i][1] else ""


def scope_s(events: Iterable[ScopedEvent], window: Tuple[float, float],
            module: str = "chunk") -> Dict[str, float]:
    """Device seconds per innermost known scope (``"unscoped"`` for the
    rest) of the operations of programs whose name holds ``module``, inside
    ``window`` (ns), control flow left out, mean over chips."""
    w0, w1 = window
    dev = tr.device_ops(events)
    out: Dict[str, float] = {}
    for ops in dev.values():
        for e in ops:
            if module not in e.module or tr.is_container(e) or e.opcode in CONTAINER_OPCODES:
                continue
            d = min(e.end_ns, w1) - max(e.start_ns, w0)
            if d > 0:
                k = scope_of(e.op_name) or "unscoped"
                out[k] = out.get(k, 0.0) + d
    n = max(len(dev), 1)
    return {k: v / n * 1e-9 for k, v in out.items()}


def span_segments(events: Iterable[tr.Event], window: Tuple[float, float]
                  ) -> List[Tuple[float, float, str]]:
    """The window cut at every program and harness span edge, each piece
    labelled by the innermost open ``adaptcl.*`` span, else the innermost
    open ``bench.*`` span, else ``"outside spans"``."""
    w0, w1 = window
    spans = [e for e in events if e.plane.startswith("/host:")
             and e.name.startswith((PROGRAM_PREFIX, HARNESS_PREFIX))
             and e.end_ns > w0 and e.start_ns < w1]
    edges = sorted({w0, w1} | {min(max(x, w0), w1) for s in spans
                               for x in (s.start_ns, s.end_ns)})
    segs = []
    for a, b in zip(edges, edges[1:]):
        mid = (a + b) / 2
        open_ = [s for s in spans if s.start_ns <= mid < s.end_ns]
        prog = [s for s in open_ if s.name.startswith(PROGRAM_PREFIX)]
        pick = prog or open_
        label = min(pick, key=lambda s: s.dur_ns).name if pick else "outside spans"
        segs.append((a, b, label))
    return segs


def _idle(events: Iterable[tr.Event], window: Tuple[float, float]
          ) -> List[Tuple[float, float]]:
    """Idle intervals of the first chip inside ``window``."""
    w0, w1 = window
    dev = tr.device_ops(events)
    if not dev:
        raise ValueError("the trace holds no device operation")
    first = dev[sorted(dev)[0]]
    busy = tr.union_ns((max(e.start_ns, w0), min(e.end_ns, w1)) for e in first
                       if e.end_ns > w0 and e.start_ns < w1)
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def _overlaps(idle: Sequence[Tuple[float, float]],
              pieces: Sequence[Tuple[float, float, str]]) -> Iterable[Tuple[str, float]]:
    """``(label, seconds)`` of each overlap of the sorted idle intervals
    with the sorted, disjoint labelled ``pieces`` (ns)."""
    i = 0
    for a, b in idle:
        while i < len(pieces) and pieces[i][1] <= a:
            i += 1
        j = i
        while j < len(pieces) and pieces[j][0] < b:
            s, e, label = pieces[j]
            if min(b, e) > max(a, s):
                yield label, (min(b, e) - max(a, s)) * 1e-9
            j += 1


def idle_by_span(events: Sequence[tr.Event], window: Tuple[float, float]
                 ) -> Dict[str, float]:
    """Idle seconds of the first chip per innermost span (``span_segments``)."""
    out: Dict[str, float] = {}
    for label, d in _overlaps(_idle(events, window), span_segments(events, window)):
        out[label] = out.get(label, 0.0) + d
    return out


def idle_under(events: Sequence[tr.Event], window: Tuple[float, float],
               names: Sequence[str]) -> float:
    """Idle seconds of the first chip inside any span named in ``names``
    (full names, spans nested in them included)."""
    cover = tr.union_ns((e.start_ns, e.end_ns) for e in events
                        if e.plane.startswith("/host:") and e.name in names)
    return sum(d for _, d in _overlaps(_idle(events, window), [(s, e, "") for s, e in cover]))
