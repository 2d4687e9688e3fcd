"""Device time of the train step by layer, and the device clock's offset
from the host's.

The program runs each layer of its train step under a ``jax.named_scope``
(``launch/steps.py``, ``models/``), so every compiled HLO instruction's
``op_name`` carries the scopes it was traced under, with transform wrappers
around them: ``jit(local_step)/transpose(jvp(blocks))/while/body/
closed_call/checkpoint/rematted_computation/attention/attention_core/...``
is the recomputed forward of ``attention_core`` inside the backward pass.

The trace names each device op by its HLO instruction (``fusion.595``); its
own event metadata also carries the instruction's ``op_name``, as the
``tf_op`` stat (``op_name:op_type``; an instruction of a loop body that has
none carries its loop's).  :func:`op_names_from_xplane` reads those into a
map from op name to ``op_name``, which the harness uses;
:func:`op_names_from_hlo` builds the same map from a compiled program's HLO
text, for checks without a chip.

:data:`SCOPES` are the ``X`` of every ``step_ms.X`` per-layer metric of
``BENCHMARK.json`` but ``unscoped`` (:func:`registered`), so that a metric
added for a new scope of the program is attributed with no edit here.

Attribution: an op goes to the innermost of :data:`SCOPES` named in its
``op_name`` path, after transform wrappers such as ``jvp(...)`` and
``transpose(...)`` are stripped (``jit(...)`` is a function's name, not a
scope, and is never stripped).  Where a fusion lists several ``;``-joined
``op_name``s, the first counts.  Ops under none of the scopes go to
``unscoped``, and so do trace ops the map does not know, whose time is
counted apart as ``unmapped``.  Times are those of leaf ops
(``devtrace.leaves``) clipped to the window, so the scopes' times add up to
the leaf-op time.

Clock bracket: the step's program cannot start on the device before the
host's ``bench.step_dispatch`` span that launched it starts, and the host's
``bench.read_loss`` span cannot end before the program has ended.  So with
the device clock reading ``host + offset``, for every step ``k``:
``module_end[k] - read_loss_end[k] <= offset <= module_start[k] -
dispatch_start[k]``; the programs are read on the device plane's ``XLA
Modules`` line.
"""
from __future__ import annotations

import json
import os
import re
from collections import Counter
from dataclasses import dataclass
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from bench import devtrace
from bench.devtrace import Op, Trace

MANIFEST = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCHMARK.json")
METRIC = "step_ms."
UNSCOPED = "unscoped"
MODULES_LINE = "XLA Modules"
DISPATCH = "bench.step_dispatch"
READ = "bench.read_loss"
LONG_GAP_NS = 1_000_000     # the gaps the clock bracket is checked against

_WRAPPED = re.compile(r"([A-Za-z_][\w.\-]*)\((.*)\)")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')


# ---------------------------------------------------------------------------
# the attribution rule
# ---------------------------------------------------------------------------


def registered(manifest: str = MANIFEST) -> Tuple[str, ...]:
    """The scopes device time is attributed to, in the manifest's order:
    the ``X`` of each per-layer metric ``step_ms.X`` but ``unscoped``."""
    with open(manifest) as f:
        metrics = [m["name"] for m in json.load(f)["per_layer"]]
    return tuple(dict.fromkeys(
        n[len(METRIC):] for n in metrics
        if n.startswith(METRIC) and n != METRIC + UNSCOPED))


SCOPES = registered()


def _components(path: str) -> List[str]:
    """``a/b(c/d)/e`` -> ``["a", "b(c/d)", "e"]``: split at slashes outside
    parentheses."""
    out, depth, cur = [], 0, []
    for ch in path:
        if ch == "/" and depth == 0:
            out.append("".join(cur))
            cur = []
            continue
        depth += (ch == "(") - (ch == ")")
        cur.append(ch)
    out.append("".join(cur))
    return out


def _unwrap(component: str) -> str:
    """``transpose(jvp(attention))`` -> ``attention``; ``jit(f)`` stays."""
    while True:
        m = _WRAPPED.fullmatch(component)
        if m is None or m.group(1) in ("jit", "pjit"):
            return component
        component = m.group(2)


def scope_of(op_name: str) -> str:
    """The innermost of :data:`SCOPES` that ``op_name`` names, else
    ``unscoped``."""
    first = op_name.split(";", 1)[0]
    for part in reversed(_components(first)):
        for sub in reversed(_components(_unwrap(part))):
            if sub in SCOPES:
                return sub
    return UNSCOPED


# ---------------------------------------------------------------------------
# op name -> op_name maps
# ---------------------------------------------------------------------------


def op_names_from_hlo(text: str) -> Dict[str, str]:
    """Each instruction of every computation of an HLO module's text, by
    its name, to its ``op_name`` ("" where it has none)."""
    out: Dict[str, str] = {}
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            continue
        op = _OP_NAME.search(line)
        out[m.group(1)] = op.group(1).replace('\\"', '"') if op else ""
    return out


def _varint(buf: bytes, pos: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[pos]
        pos += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, pos
        shift += 7


def _fields(buf: bytes, start: int = 0,
            end: Optional[int] = None) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one protobuf message: an int for varints,
    a (start, end) slice of ``buf`` for length-delimited fields."""
    pos, end = start, len(buf) if end is None else end
    while pos < end:
        key, pos = _varint(buf, pos)
        kind = key & 7
        if kind == 0:
            value, pos = _varint(buf, pos)
        elif kind == 2:
            n, pos = _varint(buf, pos)
            value, pos = (pos, pos + n), pos + n
        elif kind == 1:
            value, pos = None, pos + 8
        elif kind == 5:
            value, pos = None, pos + 4
        else:
            raise ValueError(f"unknown protobuf wire type {kind}")
        yield key >> 3, value


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _device_plane_metadata(buf: bytes, plane) -> Dict[str, str]:
    # XPlane: name 2, event_metadata 4 (map int64 -> XEventMetadata: name 2,
    # stats 5), stat_metadata 5 (map int64 -> XStatMetadata: id 1, name 2);
    # XStat: metadata_id 1, str_value 5
    names: Dict[int, str] = {}
    events = []
    for num, val in _fields(buf, *plane):
        if num not in (4, 5):
            continue
        for f, v in _fields(buf, *val):
            if f != 2:
                continue
            if num == 5:
                sid, sname = 0, ""
                for g, w in _fields(buf, *v):
                    if g == 1:
                        sid = w
                    elif g == 2:
                        sname = _text(buf, w)
                names[sid] = sname
            else:
                events.append(v)
    out: Dict[str, str] = {}
    for ev in events:
        name, stats = "", []
        for g, w in _fields(buf, *ev):
            if g == 2:
                name = _text(buf, w)
            elif g == 5:
                stats.append(w)
        for st in stats:
            sid, value = None, None
            for h, x in _fields(buf, *st):
                if h == 1:
                    sid = x
                elif h == 5:
                    value = _text(buf, x)
            if names.get(sid) == "tf_op" and value is not None:
                out.setdefault(devtrace.short_name(name),
                               value.rsplit(":", 1)[0])
                break
        else:
            if name.startswith("%"):
                out.setdefault(devtrace.short_name(name), "")
    return out


def op_names_from_xplane(path: str) -> Dict[str, str]:
    """The trace's own map from each device op's name to its ``op_name``:
    the ``tf_op`` stat of the event metadata of the ``/device:TPU:n``
    planes ("" for an op without one)."""
    with open(path, "rb") as f:
        buf = f.read()
    out: Dict[str, str] = {}
    for num, plane in _fields(buf):
        if num != 1:
            continue
        name = next((_text(buf, v) for f, v in _fields(buf, *plane)
                     if f == 2), "")
        if name.startswith(devtrace.DEVICE_PLANE):
            for k, v in _device_plane_metadata(buf, plane).items():
                out.setdefault(k, v)
    return out


def modules_from_xplane(path: str) -> Dict[str, List[Op]]:
    """Each device's programs, from its plane's ``XLA Modules`` line."""
    from jax.profiler import ProfileData
    out: Dict[str, List[Op]] = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith(devtrace.DEVICE_PLANE):
            continue
        mods = out.setdefault(plane.name[len(devtrace.DEVICE_PLANE):], [])
        for line in plane.lines:
            if line.name == MODULES_LINE:
                for ev in line.events:
                    start = int(ev.start_ns)
                    mods.append(Op(ev.name, start,
                                   start + int(ev.duration_ns)))
    return out


def load_json(path: str) -> Tuple[Trace, Dict[str, List[Op]], Dict[str, str]]:
    """A hand-written fixture: ``devtrace.load_json``'s records, plus
    ``"modules": {dev: [[name, start_ns, dur_ns], ...]}`` and
    ``"op_names": {op: op_name}``."""
    with open(path) as f:
        doc = json.load(f)
    modules = {dev: [Op(r[0], int(r[1]), int(r[1]) + int(r[2]))
                     for r in recs]
               for dev, recs in doc.get("modules", {}).items()}
    return devtrace.load_json(path), modules, doc.get("op_names", {})


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------


@dataclass
class ScopeTimes:
    ns: Dict[str, float]        # per scope and unscoped, mean over devices
    leaf_ns: float              # all leaf-op time, mean over devices
    unmapped_ns: float          # leaf-op time of ops the map lacks
    steps: int

    @property
    def scoped(self) -> bool:
        """Whether any op ran under one of the program's scopes (a
        program without them reads nothing)."""
        return any(self.ns[s] > 0 for s in SCOPES)

    def ms_per_step(self, scope: str) -> float:
        return self.ns[scope] / 1e6 / self.steps


def scope_times(trace: Trace, window: devtrace.Interval,
                op_names: Dict[str, str], steps: int,
                devices: Optional[Sequence[str]] = None) -> ScopeTimes:
    devs = sorted(trace.devices) if devices is None else list(devices)
    n = len(devs)
    ns = {s: 0.0 for s in SCOPES + (UNSCOPED,)}
    leaf = unmapped = 0.0
    scope = {name: scope_of(op) for name, op in op_names.items()}
    t0, t1 = window
    for d in devs:
        for o in devtrace.leaves(trace.devices.get(d, [])):
            dur = min(o.end, t1) - max(o.start, t0)
            if dur <= 0:
                continue
            leaf += dur / n
            if o.name not in scope:
                unmapped += dur / n
            ns[scope.get(o.name, UNSCOPED)] += dur / n
    return ScopeTimes(ns, leaf, unmapped, steps)


def clock_bracket(modules: Sequence[Op], host: Sequence[Op],
                  ) -> Optional[Tuple[int, int, int]]:
    """(low, high, steps): the bounds in ns on the device clock's offset
    from the host's, from the step program's runs on one device (the
    module name that runs most often) paired in order with the host's
    dispatch and loss-read spans; None where their counts differ."""
    if not modules:
        return None
    prog = Counter(m.name for m in modules).most_common(1)[0][0]
    runs = sorted((m for m in modules if m.name == prog),
                  key=lambda m: m.start)
    disp = [h for h in host if h.name == DISPATCH]
    reads = [h for h in host if h.name == READ]
    if not runs or not len(runs) == len(disp) == len(reads):
        return None
    low = max(m.end - r.end for m, r in zip(runs, reads))
    high = min(m.start - d.start for m, d in zip(runs, disp))
    return low, high, len(runs)


def relabelled_gaps(holes: Sequence[devtrace.Interval], host: Sequence[Op],
                    offset: int) -> int:
    """How many idle gaps change their harness label when the device's
    times are moved onto the host clock by ``offset``."""
    return sum(devtrace.label(h, host)
               != devtrace.label((h[0] - offset, h[1] - offset), host)
               for h in holes)


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


def of_run(run) -> Optional[ScopeTimes]:
    """The traced run's times by scope, reduced once per run and logged
    with the unmapped share and the clock bracket; None on an untraced run
    or where the trace holds no op under any of the program's scopes."""
    if "step_scopes" in run.data:
        return run.data["step_scopes"]
    run.data["step_scopes"] = None
    tr, steps = run.data.get("trace"), run.data.get("steps")
    if tr is None or not steps:
        return None
    from bench.run import log
    path = devtrace.find_xplane(run.data["trace_dir"])
    window = run.data["trace_window"]
    devs = [d for d in (str(x.id) for x in run.devices) if d in tr.devices]
    st = scope_times(tr, window, op_names_from_xplane(path), steps,
                     devs or None)
    share = 100.0 * st.unmapped_ns / st.leaf_ns if st.leaf_ns else 0.0
    log(f"[scopes] leaf-op time {st.leaf_ns / 1e6 / steps!r} ms a step; "
        f"unmapped {share!r}% of it; scoped {st.scoped}")
    for dev, mods in sorted(modules_from_xplane(path).items()):
        b = clock_bracket(mods, tr.host)
        if b is None:
            log(f"[scopes] device {dev}: no clock bracket (program runs "
                f"and host spans do not pair)")
            continue
        low, high, n = b
        holes = [h for h in devtrace.holes(tr.devices.get(dev, []), window)
                 if h[1] - h[0] >= LONG_GAP_NS]
        log(f"[scopes] device {dev}: clock offset from host in "
            f"[{low / 1e6!r}, {high / 1e6!r}] ms over {n} steps; of "
            f"{len(holes)} idle gaps of 1 ms or more, "
            f"{relabelled_gaps(holes, tr.host, low)} change label at the "
            f"low end, {relabelled_gaps(holes, tr.host, high)} at the high "
            f"end")
    if st.scoped:
        run.data["step_scopes"] = st
    return run.data["step_scopes"]


def step_ms(run, scope: str) -> Optional[float]:
    """Device time per step of the leaf ops under ``scope``, in ms."""
    st = of_run(run)
    return None if st is None else st.ms_per_step(scope)
