"""Reduce a JAX profiler trace to the benchmark's device numbers.

A traced run records one profile around its measured window.  ``load``
reads the ``.xplane.pb`` the profiler wrote into plain arrays: for every
device, the start and end (ns) and name of each operation on its
"XLA Ops" line, and the harness's own host spans (``TraceAnnotation``)
on the same clock.  XLA's CPU client has no device planes; there the
operations are the host events that carry an ``hlo_op`` stat, grouped by
``device_ordinal`` (what the tests record).  ``reduce`` then gives, over
the window span:

* busy time per device: the union of its operations' intervals, clipped
  to the window; ``busy_s`` is the mean over the devices used, and the
  idle share is one less busy over window;
* collective time on the first device: the time it is busy while one
  of its collective operations (all-to-all, all-reduce,
  collective-permute, all-gather, reduce-scatter, by their HLO opcodes;
  the CPU trace names some after their JAX primitives,
  ``psum``/``pmax``/``pmin``/``ppermute``) runs or, started
  asynchronously, is in flight ("Async XLA Ops");
* the operations that took most time on the first device, by name and
  by their own time (a while loop's, net of the operations of its body);
* the longest idle gaps on the first device, each named after the
  innermost harness span that covers its middle.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

import numpy as np

# HLO opcodes of collectives, and the JAX primitives XLA names them after
COLLECTIVES = ("all-to-all", "all-reduce", "collective-permute",
               "all-gather", "reduce-scatter", "psum", "pmax", "pmin",
               "ppermute")
TOP = 10
DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:(\d+)$")
# "%name = <shape> opcode(operands), ..." as the TPU trace names its ops
HLO_TEXT = re.compile(r"^%?([^ ]+) = .*? ([a-z][a-z0-9-]*)\(")


@dataclasses.dataclass
class Ops:
    start: np.ndarray     # int64 ns
    end: np.ndarray       # int64 ns
    name: list            # the HLO instruction's name
    opcode: list          # its HLO opcode


@dataclasses.dataclass
class Trace:
    devices: dict         # device id -> Ops ("XLA Ops")
    spans: list           # (name, start_ns, end_ns) host spans
    in_flight: dict = dataclasses.field(default_factory=dict)
    # device id -> Ops of asynchronous operations, start to done
    # ("Async XLA Ops"): an asynchronous collective's time in flight


def op_name(text: str) -> tuple[str, str]:
    """(name, opcode) of a trace event: the TPU trace gives the whole HLO
    instruction, the CPU trace its name alone (``sort.3``)."""
    m = HLO_TEXT.match(text)
    if m:
        return m.group(1), m.group(2)
    return text, text.rsplit(".", 1)[0]


def _ops(events) -> Ops:
    start = np.array([e[1] for e in events], np.int64)
    dur = np.array([e[2] for e in events], np.int64)
    names = [op_name(e[0]) for e in events]
    return Ops(start, start + dur, [n for n, _ in names],
               [o for _, o in names])


def load(logdir: str, span_names) -> Trace:
    """Read the newest profile under ``logdir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    span_names = set(span_names)
    devices, in_flight, spans, cpu_ops = {}, {}, [], {}
    for plane in data.planes:
        dev = DEVICE_PLANE.match(plane.name)
        if dev:
            dev = int(dev.group(1))
            for line in plane.lines:
                into = {"XLA Ops": devices,
                        "Async XLA Ops": in_flight}.get(line.name)
                if into is not None:
                    into[dev] = _ops([(e.name, int(e.start_ns),
                                       int(e.duration_ns))
                                      for e in line.events])
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in span_names:
                    start = int(e.start_ns)
                    spans.append((e.name, start, start + int(e.duration_ns)))
                    continue
                stats = dict(e.stats)
                if "hlo_op" in stats:
                    cpu_ops.setdefault(int(stats.get("device_ordinal", 0)),
                                       []).append((e.name, int(e.start_ns),
                                                   int(e.duration_ns)))
    if not devices:
        devices = {d: _ops(ev) for d, ev in cpu_ops.items()}
    return Trace(devices, spans, in_flight)


def union(start: np.ndarray, end: np.ndarray):
    """Merge intervals: sorted, disjoint (start, end) arrays."""
    if start.size == 0:
        return start, end
    order = np.argsort(start, kind="stable")
    s, e = start[order], end[order]
    reach = np.maximum.accumulate(e)
    # a new run starts where an interval begins past every earlier end
    new = np.concatenate([[True], s[1:] > reach[:-1]])
    idx = np.flatnonzero(new)
    return s[idx], np.maximum.reduceat(e, idx)


def clip(start, end, lo: int, hi: int):
    """Intervals cut to [lo, hi): (start, end, mask of those kept)."""
    s, e = np.maximum(start, lo), np.minimum(end, hi)
    keep = e > s
    return s[keep], e[keep], keep


def covered_ns(s: np.ndarray, e: np.ndarray, t: np.ndarray) -> np.ndarray:
    """For sorted disjoint intervals (s, e): the time they cover before
    each instant of ``t``."""
    csum = np.concatenate([[0], np.cumsum(e - s)])
    i = np.searchsorted(s, t, side="right")
    past = np.where(i > 0, np.maximum(e[np.maximum(i - 1, 0)] - t, 0), 0)
    return csum[i] - past


def busy_ns(ops: Ops, lo: int, hi: int) -> int:
    s, e = union(*clip(ops.start, ops.end, lo, hi)[:2])
    return int((e - s).sum())


def self_ns(start: np.ndarray, end: np.ndarray) -> np.ndarray:
    """Each interval's time less that of the intervals directly nested in
    it (a while loop's own time, net of its body's operations).  Intervals
    that only overlap, as asynchronous operations do, keep their time."""
    order = np.lexsort((-end, start))
    own = (end - start).astype(np.int64)
    stack = []
    for i in order.tolist():
        while stack and end[stack[-1]] <= start[i]:
            stack.pop()
        if stack and end[i] <= end[stack[-1]]:
            own[stack[-1]] -= end[i] - start[i]
        stack.append(i)
    return np.maximum(own, 0)


def label(spans, t: int) -> str:
    """The innermost span covering ``t``, or "none"."""
    inside = [(e - s, n) for n, s, e in spans if s <= t < e]
    return min(inside)[1] if inside else "none"


def reduce(tr: Trace, window: str = "window") -> dict:
    """The device numbers of the ``window`` span (see module doc)."""
    wins = [(s, e) for n, s, e in tr.spans if n == window]
    if not wins or not tr.devices:
        raise ValueError("the trace has no window span or no device ops")
    lo, hi = wins[0]
    ids = sorted(tr.devices)
    busy = {d: busy_ns(tr.devices[d], lo, hi) for d in ids}
    first = tr.devices[ids[0]]
    s, e, kept = clip(first.start, first.end, lo, hi)
    bs, be = union(s, e)
    # collective time: collective operations, synchronous or in flight,
    # while the device is busy
    cs, ce = [], []
    for ops in (first, tr.in_flight.get(ids[0])):
        if ops is not None and ops.start.size:
            m = np.array([o.startswith(COLLECTIVES) for o in ops.opcode])
            c = clip(ops.start[m], ops.end[m], lo, hi)
            cs.append(c[0])
            ce.append(c[1])
    cs, ce = union(np.concatenate(cs or [np.zeros(0, np.int64)]),
                   np.concatenate(ce or [np.zeros(0, np.int64)]))
    collective = int((covered_ns(bs, be, ce)
                      - covered_ns(bs, be, cs)).sum()) if bs.size else 0
    names = [n for n, k in zip(first.name, kept) if k]
    per_name = {}
    for n, dt in zip(names, self_ns(s, e).tolist()):
        per_name[n] = per_name.get(n, 0) + dt
    top = sorted(per_name.items(), key=lambda kv: -kv[1])[:TOP]
    gs = np.concatenate([[lo], be])
    ge = np.concatenate([bs, [hi]])
    keep = ge > gs
    gs, ge = gs[keep], ge[keep]
    longest = np.argsort(-(ge - gs), kind="stable")[:TOP]
    gaps = [[label(tr.spans, int((gs[i] + ge[i]) // 2)),
             float(ge[i] - gs[i]) / 1e9] for i in longest]
    window_s = (hi - lo) / 1e9
    busy_s = float(np.mean([busy[d] for d in ids])) / 1e9
    return dict(
        window_s=window_s,
        busy_s=busy_s,
        busy_s_by_device={d: busy[d] / 1e9 for d in ids},
        idle_share=1.0 - busy_s / window_s,
        first_busy_s=busy[ids[0]] / 1e9,
        collective_s=collective / 1e9,
        device_ops=[[n, dt / 1e9] for n, dt in top],
        idle_gaps=gaps,
        op_count=int(first.start.size),
    )
