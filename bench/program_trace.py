"""The program's own host spans and device scopes in a traced run.

The program names its spans and scopes in ``repro.runtime.spans``
(``HOST_SPANS``, ``DEVICE_SCOPES``); this module takes the names from
there, so they never drift. For the traced run that just ended (the newest
:class:`bench.drive.Recorder`) it loads the profiler trace once, keeping

* the host events named by the program or by the benchmark
  (``traces.HOST_SPANS`` and the window span), and
* every device operation with the program's device scope it ran under.
  A TPU trace names an operation by its HLO instruction and keeps each
  compiled module's ``HloProto`` in its metadata plane; the scope is read
  from the instruction's ``op_name`` path there
  (``jit(block)/while/body/closed_call/chain.price/...``).

Device time is given to a scope over *leaf* operations only: an operation
with another nested inside it on the same line (a ``while`` and its body,
a call and the kernel it launches) is not counted, so no time counts twice.
Device idle time is split at the host spans' edges and given, piece by
piece, to the innermost span open then (``host.other`` outside all).

Where the program opens no such span or scope (a checkout older than
``repro.runtime.spans``), every reading is None.
"""
from __future__ import annotations

import bisect
import os
from typing import Dict, List, Optional, Sequence, Tuple

from bench import drive, traces

Op = Tuple[int, int, Optional[str]]  # (start_ns, duration_ns, scope)
Event = traces.Event
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"  # a compiled module's HloProto, in the metadata plane
MODULE_LINE = "XLA Modules"

_LOADED: Dict[tuple, dict] = {}


def program_names() -> Optional[Tuple[tuple, tuple]]:
    """(host span names, device scope names) of the program, or None where
    the checkout's program has none."""
    try:
        from repro.runtime import spans
    except ImportError:
        return None
    return tuple(spans.HOST_SPANS), tuple(spans.DEVICE_SCOPES)


def scope_of(path: str, scopes: Sequence[str]) -> Optional[str]:
    """The one of ``scopes`` named in an operation's name path
    (``jit(block)/while/body/chain.price/...``), or None."""
    return next((s for s in scopes if f"/{s}/" in path or path.endswith(f"/{s}")), None)


# -- the few protobuf messages read here, by field number --------------------
# XSpace.planes 1; XPlane.name 2, .event_metadata 4 (map entry: key 1,
# value 2), .stat_metadata 5 (same); XEventMetadata.name 2, .stats 5;
# XStatMetadata.name 2; XStat.metadata_id 1, .bytes_value 6;
# HloProto.hlo_module 1; HloModuleProto.computations 3;
# HloComputationProto.instructions 2; HloInstructionProto.name 1,
# .metadata 7; OpMetadata.op_name 2.


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of one protobuf message: an int
    for a varint, a memoryview for a length-delimited field."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            v, i = buf[i:i + n], i + n
        elif kind in (1, 5):
            n = 8 if kind == 1 else 4
            v, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"protobuf wire type {kind} is not read here")
        yield key >> 3, v


def _field(buf, number: int):
    return next((v for f, v in _fields(buf) if f == number), None)


def op_paths(xspace: bytes) -> Dict[str, Dict[str, str]]:
    """Module name -> {instruction name: op_name path}, from the compiled
    modules' HloProtos the trace keeps in its metadata plane."""
    out: Dict[str, Dict[str, str]] = {}
    for f, plane in _fields(memoryview(xspace)):
        if f != 1 or bytes(_field(plane, 2) or b"").decode() != METADATA_PLANE:
            continue
        stat_ids = {_field(entry, 1) for g, entry in _fields(plane) if g == 5
                    if bytes(_field(_field(entry, 2), 2) or b"").decode() == HLO_PROTO_STAT}
        for g, entry in _fields(plane):
            if g != 4:
                continue
            meta = _field(entry, 2)
            name = bytes(_field(meta, 2) or b"").decode()
            for h, stat in _fields(meta):
                if h == 5 and _field(stat, 1) in stat_ids:
                    module = _field(_field(stat, 6), 1)
                    paths = out.setdefault(name, {})
                    for c, comp in _fields(module):
                        if c != 3:
                            continue
                        for k, ins in _fields(comp):
                            if k == 2:
                                md = _field(ins, 7)
                                op = _field(md, 2) if md is not None else None
                                paths[bytes(_field(ins, 1)).decode()] = bytes(op or b"").decode()
    return out


def _instruction(event_name: str) -> str:
    """``%fusion.366 = f32[...] fusion(...)`` -> ``fusion.366``."""
    return event_name.split(" ", 1)[0].lstrip("%")


def load(path: str, host_names: Sequence[str], scopes: Sequence[str]) -> dict:
    """Device operations per device plane (with the scope each ran under)
    and the host events named in ``host_names`` or by the benchmark;
    loaded once per file."""
    key = (path, os.stat(path).st_mtime_ns, tuple(host_names), tuple(scopes))
    if key in _LOADED:
        return _LOADED[key]
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        raw = f.read()
    paths = op_paths(raw)
    keep = set(host_names) | set(traces.HOST_SPANS) | {traces.WINDOW_SPAN}
    devices: Dict[str, List[Op]] = {}
    host: List[Event] = []
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {line.name: line for line in plane.lines}
            want = next((n for n in traces.OP_LINES if n in lines), None)
            if want is None:
                continue
            modules = sorted((int(e.start_ns), int(e.end_ns), e.name)
                             for e in (lines[MODULE_LINE].events if MODULE_LINE in lines else ()))
            starts = [m[0] for m in modules]
            cache: Dict[tuple, Optional[str]] = {}
            ops: List[Op] = []
            for e in lines[want].events:
                s = int(e.start_ns)
                k = bisect.bisect_right(starts, s) - 1
                module = modules[k][2] if k >= 0 and s < modules[k][1] else None
                ck = (module, e.name)
                if ck not in cache:
                    op = paths.get(module, {}).get(_instruction(e.name), "")
                    cache[ck] = scope_of(op, scopes)
                ops.append((s, int(e.duration_ns), cache[ck]))
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in keep:
                        host.append((e.name, int(e.start_ns), int(e.duration_ns)))
    _LOADED.clear()  # one run's trace at a time
    _LOADED[key] = {"devices": devices, "host": host}
    return _LOADED[key]


def window(host: Sequence[Event]) -> Tuple[int, int]:
    wins = [(s, s + d) for name, s, d in host if name == traces.WINDOW_SPAN]
    if not wins:
        raise ValueError("trace has no window span")
    return wins[0]


def leaves(ops: Sequence[Op]) -> List[Op]:
    """The operations with no other operation nested inside them."""
    ordered = sorted(ops, key=lambda o: (o[0], -o[1]))
    out = []
    for i, op in enumerate(ordered):
        end = op[0] + op[1]
        if i + 1 == len(ordered) or ordered[i + 1][0] >= end:
            out.append(op)
    return out


def scope_time(devices: Dict[str, List[Op]], w0: int, w1: int) -> Dict[Optional[str], float]:
    """Seconds of leaf-operation time inside the window by device scope
    (None: outside every scope), averaged over the device planes."""
    out: Dict[Optional[str], float] = {}
    for ops in devices.values():
        for s, d, scope in leaves(ops):
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                out[scope] = out.get(scope, 0.0) + (b - a) * 1e-9 / len(devices)
    return out


def idle_by_span(devices: Dict[str, List[Op]], host: Sequence[Event],
                 w0: int, w1: int) -> Tuple[float, Dict[str, float]]:
    """Busy seconds of the first device plane in the window, and its idle
    seconds split at the host spans' edges, each piece given to the
    innermost span open in it (``host.other`` where none is)."""
    ops = sorted(devices.items())[0][1]
    busy = traces._union([(max(s, w0), min(s + d, w1)) for s, d, _ in ops
                          if min(s + d, w1) > max(s, w0)])
    spans = [(name, s, s + d) for name, s, d in host if name != traces.WINDOW_SPAN]
    edges = sorted({w0, w1} | {t for _, a, b in spans for t in (a, b) if w0 < t < w1})
    idle: Dict[str, float] = {}
    k = 0
    for a, b in zip(edges, edges[1:]):
        open_ = [(e - s, name) for name, s, e in spans if s <= a and b <= e]
        label = min(open_)[1] if open_ else "host.other"
        free = b - a
        while k < len(busy) and busy[k][1] <= a:
            k += 1
        j = k
        while j < len(busy) and busy[j][0] < b:
            free -= min(b, busy[j][1]) - max(a, busy[j][0])
            j += 1
        if free > 0:
            idle[label] = idle.get(label, 0.0) + free * 1e-9
    return sum(b - a for a, b in busy) * 1e-9, idle


def of(w) -> Optional[dict]:
    """The loaded trace of a traced ``searches`` run, with its window
    (``w0``, ``w1``) and, once asked for, its ``scope_time`` and
    ``idle``; None without a trace, in another mode, or for a program
    without spans."""
    if w.mode != "searches" or w.trace is None or not drive._RECORDERS:
        return None
    names = program_names()
    rec = drive._RECORDERS[-1]
    if names is None or rec.trace_dir is None:
        return None
    try:
        path = traces.find_xplane(rec.trace_dir)
    except FileNotFoundError:
        return None
    ev = load(path, *names)
    if "w0" not in ev:
        ev["w0"], ev["w1"] = window(ev["host"])
    return ev


def n_blocks(w) -> int:
    return sum(1 for name, *_ in w.spans if name == "chains.block")


def span_ms(w, names: Sequence[str]) -> Optional[float]:
    """Host time inside the window in the program's spans ``names``, per
    chain block of the window, in ms."""
    ev = of(w)
    if ev is None or not n_blocks(w):
        return None
    d = [dur for name, s, dur in ev["host"] if name in names and ev["w0"] <= s < ev["w1"]]
    return 1e-6 * sum(d) / n_blocks(w) if d else None


def idle_ms(w, names: Sequence[str]) -> Optional[float]:
    """Device idle time while one of the program's spans ``names`` was the
    innermost open, per chain block of the window, in ms."""
    ev = of(w)
    if ev is None or not n_blocks(w) or not ev["devices"]:
        return None
    if not any(name in names for name, *_ in ev["host"]):
        return None
    if "idle" not in ev:
        ev["idle"] = idle_by_span(ev["devices"], ev["host"], ev["w0"], ev["w1"])[1]
    return 1e3 * sum(ev["idle"].get(n, 0.0) for n in names) / n_blocks(w)


def scope_ms(w, scopes: Sequence[str]) -> Optional[float]:
    """Leaf device time under the device scopes ``scopes``, per chain block
    of the window, in ms; None where no operation carries a scope."""
    ev = of(w)
    if ev is None or not n_blocks(w) or not ev["devices"]:
        return None
    if "scope_time" not in ev:
        ev["scope_time"] = scope_time(ev["devices"], ev["w0"], ev["w1"])
    if not any(k is not None for k in ev["scope_time"]):
        return None
    return 1e3 * sum(ev["scope_time"].get(s, 0.0) for s in scopes) / n_blocks(w)
