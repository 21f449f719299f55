"""From a profiler trace to device busy time, op time, collectives and
idle gaps.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with nothing but
``jax.profiler.ProfileData``. Device planes are ``/device:TPU:<n>``;
their ``XLA Ops`` line holds the ops the core ran one after another,
and ``Async XLA Ops`` the asynchronous copies and collectives from
their start to their done. Host spans come from the ``/host:CPU`` plane
(``jax.profiler.TraceAnnotation`` lands there). All event times are
nanoseconds on one clock.

Definitions, per device and clipped to the window:

- busy: the union of the ``XLA Ops`` intervals;
- op time: the summed durations of each op, named ``<instruction>
  <opcode>`` (``fusion.12 fusion``);
- collective: an op whose opcode is a collective (``collective-permute``,
  ``all-reduce``, ...), on either line; exposed collective time is the
  part of their union that no other op covers;
- gaps: the window minus busy, each part of a gap named by the
  shortest host span that covers it, ``"none"`` where no span does.
"""
from __future__ import annotations

import re
from bisect import bisect_left
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

__all__ = ["Trace", "load", "union", "subtract", "summarize", "opcode",
           "top"]

_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_INSTR = re.compile(r"^%?([^\s=]+) = .*? ([a-z][\w\-.]*)\(")
_COLLECTIVES = ("collective-permute", "all-reduce", "all-gather",
                "reduce-scatter", "all-to-all", "collective-broadcast",
                "send", "recv")

Interval = Tuple[float, float]


@dataclass
class Trace:
    """What the reduction needs of one trace: per device, the ops of the
    core ``(name, start, end)`` and the asynchronous ops; the host spans
    ``(name, start, end)``."""
    ops: Dict[int, List[Tuple[str, float, float]]] = field(
        default_factory=dict)
    async_ops: Dict[int, List[Tuple[str, float, float]]] = field(
        default_factory=dict)
    host: List[Tuple[str, float, float]] = field(default_factory=list)


def opcode(name: str) -> Tuple[str, str]:
    """``(instruction, opcode)`` of an HLO op event's name, which is the
    instruction's text (``%fusion.3 = f32[..] fusion(..), kind=..``);
    a name that is not HLO text is its own instruction and opcode."""
    m = _INSTR.match(name)
    if m is None:
        return name, name
    return m.group(1), m.group(2)


def load(path: str, host_names: Optional[Iterable[str]] = None) -> Trace:
    """Read an ``.xplane.pb``. ``host_names`` keeps only host spans of
    those names (all host spans when None)."""
    from jax.profiler import ProfileData

    keep = None if host_names is None else set(host_names)
    tr = Trace()
    for plane in ProfileData.from_file(path).planes:
        m = _DEVICE.match(plane.name)
        if m is not None:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dst = tr.ops.setdefault(dev, [])
                elif line.name == "Async XLA Ops":
                    dst = tr.async_ops.setdefault(dev, [])
                else:
                    continue
                dst.extend((e.name, e.start_ns, e.end_ns)
                           for e in line.events)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                tr.host.extend((e.name, e.start_ns, e.end_ns)
                               for e in line.events
                               if keep is None or e.name in keep)
    return tr


def union(iv: Sequence[Interval]) -> List[Interval]:
    """Sorted, disjoint union of intervals."""
    out: List[Interval] = []
    for a, b in sorted(iv):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _length(iv: Sequence[Interval]) -> float:
    return float(sum(b - a for a, b in iv))


def subtract(a: Sequence[Interval], b: Sequence[Interval]
             ) -> List[Interval]:
    """``a`` minus ``b``; both sorted and disjoint (as ``union`` gives)."""
    out: List[Interval] = []
    j = 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def _clip(evs, lo: float, hi: float):
    for name, s, e in evs:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            yield name, s, e


def _is_collective(op: str) -> bool:
    return op.startswith(_COLLECTIVES)


def _label_segments(spans: Sequence[Tuple[str, float, float]]):
    """Cut the time line at every span boundary and label each piece by
    the shortest span that covers it (``"none"`` where none does)."""
    bounds = sorted({t for _, s, e in spans for t in (s, e)})
    label = ["none"] * max(len(bounds) - 1, 0)
    for name, s, e in sorted(spans, key=lambda x: x[1] - x[2]):
        for k in range(bisect_left(bounds, s), bisect_left(bounds, e)):
            label[k] = name                # shorter spans paint last
    return bounds, label


def _name_gaps(gaps: List[Interval],
               spans: Sequence[Tuple[str, float, float]]
               ) -> Dict[str, float]:
    """Seconds of gap per name of the shortest host span covering each
    part of each gap; ``"none"`` for parts no span covers."""
    bounds, label = _label_segments(spans)
    out: Dict[str, float] = {}

    def add(name: str, ns: float) -> None:
        if ns > 0:
            out[name] = out.get(name, 0.0) + ns / 1e9

    k = 0
    for s, e in gaps:
        if not bounds or e <= bounds[0] or s >= bounds[-1]:
            add("none", e - s)
            continue
        add("none", max(0.0, bounds[0] - s) + max(0.0, e - bounds[-1]))
        k = max(0, min(k, len(label) - 1))
        while k > 0 and bounds[k] > s:
            k -= 1
        while k < len(label) and bounds[k] < e:
            add(label[k], min(e, bounds[k + 1]) - max(s, bounds[k]))
            k += 1
    return out


def summarize(tr: Trace, window: Interval,
              spans: Sequence[Tuple[str, float, float]] = ()) -> Dict:
    """Per-device busy, op, collective and gap totals over ``window``
    (ns), averaged over the devices that ran any op. ``spans`` names the
    gaps (host spans on the trace's clock). Times come back in
    seconds."""
    lo, hi = window
    devs = sorted(d for d in tr.ops if tr.ops[d])
    busy, coll, exposed = [], [], []
    op_s: Dict[str, float] = {}
    gap_s: Dict[str, float] = {}
    for d in devs:
        ops = list(_clip(tr.ops[d], lo, hi))
        asy = list(_clip(tr.async_ops.get(d, ()), lo, hi))
        busy_iv = union([(s, e) for _, s, e in ops])
        busy.append(_length(busy_iv))
        compute, cl = [], []
        for name, s, e in ops:
            instr, op = opcode(name)
            key = f"{instr} {op}" if instr != op else op
            op_s[key] = op_s.get(key, 0.0) + (e - s) / 1e9
            (cl if _is_collective(op) else compute).append((s, e))
        cl += [(s, e) for name, s, e in asy
               if _is_collective(opcode(name)[1])]
        cl_iv = union(cl)
        coll.append(_length(cl_iv))
        exposed.append(_length(subtract(cl_iv, union(compute))))
        for k, v in _name_gaps(subtract([(lo, hi)], busy_iv),
                               spans).items():
            gap_s[k] = gap_s.get(k, 0.0) + v
    n = max(len(devs), 1)
    return {
        "devices": len(devs),
        "window_s": (hi - lo) / 1e9,
        "busy_s": float(np.mean(busy)) / 1e9 if busy else 0.0,
        "collective_s": float(np.mean(coll)) / 1e9 if coll else 0.0,
        "exposed_collective_s": (float(np.mean(exposed)) / 1e9
                                 if exposed else 0.0),
        "op_s": {k: v / n for k, v in op_s.items()},
        "gap_s": {k: v / n for k, v in gap_s.items()},
    }


def top(d: Dict[str, float], k: int = 10) -> List[List]:
    """The ``k`` largest entries as ``[[name, seconds], ...]``."""
    return [[name, v] for name, v in
            sorted(d.items(), key=lambda kv: -kv[1])[:k]]
