"""Writes ``built.xplane.pb``: a small trace in the profiler's XSpace format,
one host plane with the benchmark's spans and one TPU plane with operations,
laid out as the busy-time test in ``test_bench_trace.py`` expects.

    python bench/tests/data/build_trace.py bench/tests/data/built.xplane.pb

Needs the XSpace protobuf module that TensorFlow ships."""
import sys
from tensorflow.tsl.profiler.protobuf import xplane_pb2

MS = 1_000_000_000  # ps per ms

def plane(space, pid, name, lines):
    p = space.planes.add(id=pid, name=name)
    meta = {}
    for lid, (lname, events) in enumerate(lines):
        line = p.lines.add(id=lid, display_id=lid, name=lname, timestamp_ns=1_000_000_000)
        for ename, start_ms, dur_ms in events:
            if ename not in meta:
                mid = len(meta) + 1
                meta[ename] = mid
                p.event_metadata[mid].id = mid
                p.event_metadata[mid].name = ename
            line.events.add(metadata_id=meta[ename], offset_ps=int(start_ms * MS), duration_ps=int(dur_ms * MS))

space = xplane_pb2.XSpace()
plane(space, 1, "/host:CPU", [("python", [
    ("bench.window", 10, 100), ("search", 10, 100),
    ("chains.block", 20, 30), ("chains.block", 60, 30)])])
plane(space, 2, "/device:TPU:0", [("XLA Modules", [("jit_block", 22, 25), ("jit_block", 62, 20)]),
                                  ("XLA Ops", [("fusion.1", 22, 20), ("fusion.2", 45, 2), ("kernel", 62, 20)])])
open(sys.argv[1], "wb").write(space.SerializeToString())
