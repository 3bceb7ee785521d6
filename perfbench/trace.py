"""Reduce a profiler trace to device busy time, idle gaps and kernel time.

The loader turns JAX's ``.xplane.pb`` into plain tuples, so that every
reduction below is a function of lists of intervals and can be checked on
a small synthetic trace:

* ``Trace.devices``  {device index: [Op(name, start_ns, end_ns, hlo_op)]},
  the innermost ops of each TPU core's ``XLA Ops`` line.  That line names
  an op by its whole HLO instruction (``%fusion.3 = f32[...] fusion(...)``),
  of which ``hlo_op`` keeps the name; it also holds the ops that contain
  others (a ``while`` spans its body), and those are left out, so that no
  time counts twice;
* ``Trace.spans``    [Span(name, start_ns, end_ns)], the host spans the
  harness writes with ``jax.profiler.TraceAnnotation`` (``bench.*``).

All times are nanoseconds on the trace's clock.
"""

from __future__ import annotations

import glob
import os
import re
from typing import NamedTuple

__all__ = ["Op", "Span", "Trace", "load_trace", "hlo_name", "innermost",
           "union_ns", "clip",
           "busy_ns", "window_of", "kernel_ns", "top_ops", "idle_gaps"]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench."
INSTRUCTION = re.compile(r"^%?([^\s=]+)\s*=")


class Op(NamedTuple):
    name: str
    start: int
    end: int
    hlo_op: str


class Span(NamedTuple):
    name: str
    start: int
    end: int


class Trace(NamedTuple):
    devices: dict
    spans: list


def load_trace(log_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    devices, spans = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                devices[int(m.group(1))] = innermost(
                    Op(e.name, int(e.start_ns), int(e.end_ns),
                       hlo_name(e.name)) for e in line.events)
            elif plane.name.startswith("/host:"):
                spans += [Span(e.name, int(e.start_ns), int(e.end_ns))
                          for e in line.events
                          if e.name.startswith(HOST_PREFIX)]
    spans.sort(key=lambda s: s.start)
    return Trace(devices, spans)


def hlo_name(text: str) -> str:
    """'%fusion.3 = f32[8] fusion(...)' -> 'fusion.3'."""
    m = INSTRUCTION.match(text)
    return m.group(1) if m else text


def innermost(ops) -> list[Op]:
    """The ops that contain no other op, sorted by start."""
    ops = sorted(ops, key=lambda o: (o.start, -o.end))
    out = []
    for i, o in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if nxt is None or nxt.start >= o.end or nxt.end > o.end:
            out.append(o)
    return out


def union_ns(intervals) -> list[tuple[int, int]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _total(intervals) -> int:
    return sum(e - s for s, e in intervals)


def clip(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def window_of(spans) -> tuple[int, int]:
    """The timed window: from the first round's dispatch to the end of the
    last round's loss pull."""
    dispatch = [s for s in spans if s.name == "bench.dispatch"]
    pulls = [s for s in spans if s.name == "bench.loss_pull"]
    if not dispatch or not pulls:
        raise ValueError("the trace holds no bench.dispatch / "
                         "bench.loss_pull spans")
    return dispatch[0].start, pulls[-1].end


def busy_ns(ops, lo: int, hi: int) -> int:
    """Time in [lo, hi) in which some op runs on the device."""
    return _total(union_ns(clip([(o.start, o.end) for o in ops], lo, hi)))


def kernel_ns(ops, names, lo: int, hi: int) -> tuple[int, int]:
    """(summed device time, number of calls) of the ops whose HLO name is
    in ``names``, within [lo, hi)."""
    hits = [o for o in ops if o.hlo_op in names or o.name in names]
    spans = clip([(o.start, o.end) for o in hits], lo, hi)
    return _total(spans), len(spans)


def _subtract(a, b) -> list[tuple[int, int]]:
    """Disjoint sorted intervals ``a`` minus disjoint sorted ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def top_ops(ops, lo: int, hi: int, n: int = 10) -> list[list]:
    """The ``n`` HLO ops that took most device time in [lo, hi), with their
    seconds."""
    per: dict[str, int] = {}
    for o in ops:
        for s, e in clip([(o.start, o.end)], lo, hi):
            per[o.hlo_op] = per.get(o.hlo_op, 0) + e - s
    ranked = sorted(per.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def idle_gaps(ops, spans, lo: int, hi: int, n: int = 10) -> list[list]:
    """The ``n`` longest stretches of [lo, hi) in which the device ran no
    op, each named by the host span that covers most of it ('host' where
    none does), with their seconds."""
    busy = union_ns(clip([(o.start, o.end) for o in ops], lo, hi))
    gaps = _subtract([(lo, hi)], busy)
    named = []
    for s, e in gaps:
        best, cover = "host", 0
        for sp in spans:
            c = min(e, sp.end) - max(s, sp.start)
            if c > cover:
                best, cover = sp.name, c
        named.append([best, (e - s) / 1e9])
    return sorted(named, key=lambda g: -g[1])[:n]
