"""Reduce a ``jax.profiler`` trace to the benchmark's device numbers.

A trace is read into plain records first (:func:`load_xplane`), so the
arithmetic below runs the same on a recorded ``.xplane.pb`` and on a
hand-written JSON fixture (:func:`load_json`):

    {"devices": {"0": [[name, start_ns, dur_ns], ...], ...},
     "host":    [[name, start_ns, dur_ns], ...]}

Device records are the ops that ran on a chip ("XLA Ops" lines of the
``/device:TPU:n`` planes), named by the HLO instruction (``fusion.12``,
``all-reduce.3``, ``scatter_add_pallas.1``; the trace's own names are the
whole HLO text); host records are the harness's own ``TraceAnnotation``
spans (names starting ``bench.``), on the same clock.  The ops line nests:
a ``while`` or ``conditional`` op spans the ops of its body, so the
reductions that attribute time use leaf ops only.

Definitions (all within a window ``[t0, t1]``, per device):

* busy: the length of the union of the op intervals; idle share is
  ``1 - busy / window``;
* op time: summed durations per op name, of leaf ops;
* exposed collective time: the length of the union of collective leaf op
  intervals minus the part of it that any other leaf op's interval covers;
* idle gaps: the holes of the busy union, each labelled by the harness
  span that was open on the host at the gap's midpoint (the innermost,
  i.e. latest-starting, one), with how many spans of that name were open
  when more than one was (worker threads).
"""
from __future__ import annotations

import glob
import json
import os
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

COLLECTIVE_MARKS = ("all-reduce", "all-gather", "reduce-scatter",
                    "collective-permute", "all-to-all")
HOST_PREFIX = "bench."
OPS_LINE = "XLA Ops"
DEVICE_PLANE = "/device:TPU:"

Interval = Tuple[int, int]


@dataclass(frozen=True)
class Op:
    name: str
    start: int          # ns
    end: int            # ns


@dataclass
class Trace:
    devices: Dict[str, List[Op]]
    host: List[Op]

    def window(self, name: str = "bench.window") -> Optional[Interval]:
        """The first host span called ``name``, as (start, end) in ns."""
        for h in self.host:
            if h.name == name:
                return h.start, h.end
        return None


def is_collective(name: str) -> bool:
    return any(name.startswith(m) for m in COLLECTIVE_MARKS)


def short_name(hlo_text: str) -> str:
    """``%fusion.595 = (...) fusion(...), kind=kOutput, ...`` ->
    ``fusion.595``."""
    return hlo_text.split(" = ", 1)[0].strip().lstrip("%")


def leaves(ops: Sequence[Op]) -> List[Op]:
    """Ops with no other op of the same device inside their interval."""
    order = sorted(ops, key=lambda o: (o.start, -o.end))
    parent = [False] * len(order)
    stack: List[int] = []
    for i, o in enumerate(order):
        while stack and order[stack[-1]].end <= o.start:
            stack.pop()
        if stack and o.end <= order[stack[-1]].end:
            parent[stack[-1]] = True
        stack.append(i)
    return [o for o, p in zip(order, parent) if not p]


# ---------------------------------------------------------------------------
# loading
# ---------------------------------------------------------------------------


def find_xplane(log_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    return paths[-1] if paths else None


def load_xplane(path: str) -> Trace:
    """Device ops and harness host spans of one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[str, List[Op]] = {}
    host: List[Op] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            dev = plane.name[len(DEVICE_PLANE):]
            ops = devices.setdefault(dev, [])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    start = int(ev.start_ns)
                    ops.append(Op(short_name(ev.name), start,
                                  start + int(ev.duration_ns)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        start = int(ev.start_ns)
                        host.append(Op(ev.name, start,
                                       start + int(ev.duration_ns)))
    return Trace(devices, sorted(host, key=lambda o: o.start))


def load_json(path: str) -> Trace:
    with open(path) as f:
        doc = json.load(f)
    devices = {dev: [Op(r[0], int(r[1]), int(r[1]) + int(r[2]))
                     for r in recs]
               for dev, recs in doc["devices"].items()}
    host = [Op(r[0], int(r[1]), int(r[1]) + int(r[2])) for r in doc["host"]]
    return Trace(devices, sorted(host, key=lambda o: o.start))


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------


def clip(ops: Iterable[Op], window: Interval) -> List[Interval]:
    t0, t1 = window
    out = []
    for o in ops:
        s, e = max(o.start, t0), min(o.end, t1)
        if e > s:
            out.append((s, e))
    return out


def union(intervals: Iterable[Interval]) -> List[Interval]:
    merged: List[List[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def length(intervals: Iterable[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of the union ``a`` not covered by the union ``b`` (both
    merged and sorted)."""
    out = []
    j = 0
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


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------


def busy_ns(ops: Iterable[Op], window: Interval) -> int:
    return length(union(clip(ops, window)))


def op_time_ns(ops: Sequence[Op], window: Interval) -> Dict[str, int]:
    out: Dict[str, int] = {}
    t0, t1 = window
    for o in leaves(ops):
        d = min(o.end, t1) - max(o.start, t0)
        if d > 0:
            out[o.name] = out.get(o.name, 0) + d
    return out


def exposed_collective_ns(ops: Sequence[Op], window: Interval) -> int:
    leaf = leaves(ops)
    coll = union(clip((o for o in leaf if is_collective(o.name)), window))
    other = union(clip((o for o in leaf if not is_collective(o.name)),
                       window))
    return length(subtract(coll, other))


def holes(ops: Sequence[Op], window: Interval) -> List[Interval]:
    """The gaps in the busy union within the window."""
    return subtract([window], union(clip(ops, window)))


def label(hole: Interval, host: Sequence[Op]) -> str:
    mid = (hole[0] + hole[1]) // 2
    open_ = [h for h in host if h.start <= mid < h.end]
    if not open_:
        return "host: no bench span open"
    name = max(open_, key=lambda h: h.start).name
    k = sum(h.name == name for h in open_)
    return name if k == 1 else f"{name} x{k}"


def idle_gaps(ops: Sequence[Op], host: Sequence[Op],
              window: Interval) -> List[Tuple[str, int]]:
    """(label, ns) of every hole in the busy union within the window."""
    return [(label(h, host), h[1] - h[0]) for h in holes(ops, window)]


@dataclass
class DeviceSummary:
    window_ns: int
    n_devices: int
    busy_ns: float                      # mean over devices
    exposed_collective_ns: float        # mean over devices
    op_ns: Dict[str, float]             # mean over devices, per op name
    holes: List[Interval]               # every idle gap of every device
    host: List[Op]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_ns / self.window_ns

    @property
    def gaps(self) -> List[Tuple[str, int]]:
        return [(label(h, self.host), h[1] - h[0]) for h in self.holes]

    def top_ops(self, n: int = 10) -> List[List]:
        top = sorted(self.op_ns.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns / 1e9] for name, ns in top]

    def top_gaps(self, n: int = 10) -> List[List]:
        top = sorted(self.holes, key=lambda h: h[0] - h[1])[:n]
        return [[label(h, self.host), (h[1] - h[0]) / 1e9] for h in top]


def summarize(trace: Trace, window: Interval,
              devices: Optional[Sequence[str]] = None) -> DeviceSummary:
    devs = sorted(trace.devices) if devices is None else list(devices)
    if not devs:
        raise ValueError("the trace holds no device plane")
    n = len(devs)
    busy = exposed = 0
    op_ns: Dict[str, float] = {}
    gaps: List[Interval] = []
    for d in devs:
        ops = trace.devices.get(d, [])
        busy += busy_ns(ops, window)
        exposed += exposed_collective_ns(ops, window)
        for name, ns in op_time_ns(ops, window).items():
            op_ns[name] = op_ns.get(name, 0.0) + ns / n
        gaps.extend(holes(ops, window))
    return DeviceSummary(window[1] - window[0], n, busy / n, exposed / n,
                         op_ns, gaps, trace.host)
