"""The program's spans and scopes in a trace: device time goes to a scope
over leaf operations only, idle time is split at the host spans' edges and
given to the innermost span, and each reader of them returns None where it
has nothing to read."""
import types

import pytest

from bench import drive, program_trace, traffic

ROOT = traffic.Path(__file__).resolve().parents[2]
MS = 1_000_000  # ns
READERS = ("chains.prep_ms", "chains.readback_ms", "chains.prep_idle_ms",
           "chains.wait_idle_ms", "phase_sim.device_ms.chains", "chains.moves_device_ms")
PATH = "jit(block)/jit(main)/while/body/{}/op"


def _reader(name):
    return traffic.load_module(ROOT, "metrics", name).read


def test_scope_of_finds_the_scope_named_in_the_path():
    scopes = ("chain.sample", "chain.price")
    assert program_trace.scope_of(PATH.format("chain.price"), scopes) == "chain.price"
    assert program_trace.scope_of("jit(block)/while/body/chain.sample", scopes) == "chain.sample"
    assert program_trace.scope_of("jit(block)/while/body/add", scopes) is None
    assert program_trace.scope_of("x/chain.pricey/op", scopes) is None


def test_device_time_goes_to_scopes_over_leaf_operations_only():
    ops = [(0, 100 * MS, None),  # a while: holds the whole body
           (10 * MS, 20 * MS, "chain.price"),
           (30 * MS, 10 * MS, "chain.accept"),
           (45 * MS, 50 * MS, "chain.price"),  # a call: holds the kernel
           (50 * MS, 40 * MS, "chain.price"),  # the kernel
           (96 * MS, 2 * MS, None),  # the loop's condition
           (140 * MS, 20 * MS, "chain.price")]  # past the window
    assert program_trace.leaves(ops) == [ops[i] for i in (1, 2, 4, 5, 6)]
    t = program_trace.scope_time({"/device:TPU:0": ops}, 0, 150 * MS)
    assert t == pytest.approx({"chain.price": 0.070, "chain.accept": 0.010, None: 0.002})


def test_idle_is_split_at_span_edges_and_given_to_the_innermost_span():
    host = [("bench.window", 0, 100 * MS),
            ("search", 0, 100 * MS),
            ("chains.block", 0, 95 * MS),
            ("chains.prep", 0, 10 * MS),
            ("chains.dispatch", 10 * MS, 2 * MS),
            ("chains.wait", 12 * MS, 68 * MS),
            ("chains.readback", 80 * MS, 15 * MS)]
    ops = [(11 * MS, 68 * MS, None), (20 * MS, 10 * MS, "chain.price")]
    busy, idle = program_trace.idle_by_span({"/device:TPU:0": ops}, host, 0, 100 * MS)
    assert busy == pytest.approx(0.068)
    assert idle == pytest.approx({"chains.prep": 0.010, "chains.dispatch": 0.001,
                                  "chains.wait": 0.001, "chains.readback": 0.015,
                                  "search": 0.005})
    assert sum(idle.values()) + busy == pytest.approx(0.1)


def _window(mode="searches", trace=True, blocks=2):
    spans = [("chains.block", 0.0, 0.1, 1)] * blocks
    return types.SimpleNamespace(mode=mode, trace={"busy_s": 1.0} if trace else None,
                                 spans=spans)


@pytest.mark.parametrize("name", READERS)
def test_each_reader_returns_none_outside_searches_or_without_a_trace(name, monkeypatch):
    monkeypatch.setattr(drive, "_RECORDERS", [])
    read = _reader(name)
    assert read(_window(mode="sessions")) is None
    assert read(_window(trace=False)) is None
    assert read(_window()) is None  # no recorder
    rec = types.SimpleNamespace(trace_dir=None)
    monkeypatch.setattr(drive, "_RECORDERS", [rec])
    assert read(_window()) is None  # an untraced recorder


def _msg(*fields):
    """A protobuf message: (field number, int | str | bytes) pairs."""
    def varint(n):
        out = b""
        while True:
            out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
            n >>= 7
            if not n:
                return out
    out = b""
    for number, v in fields:
        if isinstance(v, int):
            out += varint(number << 3) + varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += varint(number << 3 | 2) + varint(len(v)) + v
    return out


def _hlo_proto(paths):
    """An HloProto whose one computation holds instructions named as the
    keys of ``paths``, each with its op_name path."""
    comp = _msg((1, "body"), *[(2, _msg((1, name), (7, _msg((2, path)))))
                               for name, path in paths.items()])
    return _msg((1, _msg((1, "jit_block"), (3, comp))))


def _event(meta, start_ms, dur_ms):
    return (f"events {{ metadata_id: {meta} offset_ps: {int(start_ms * 1e9)} "
            f"duration_ps: {int(dur_ms * 1e9)} }}")


def _plane(pid, name, lines, names, extra=""):
    metas = "".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }} '
                    for n, i in names.items())
    body = "".join(f'lines {{ id: {k} name: "{ln}" timestamp_ns: 1000000000 {" ".join(evs)} }} '
                   for k, (ln, evs) in enumerate(lines))
    return f'planes {{ id: {pid} name: "{name}" {body} {metas} {extra} }}'


def _write_trace(tmp_path, with_program=True):
    """Two chain blocks of 40 ms in a 100 ms window, each: prep 5 ms
    (device idle), dispatch 1 ms, wait 30 ms, readback 4 ms; the device
    starts 2 ms into the wait and runs 30 ms (a while holding three scoped
    operations)."""
    host_names = {n: i for i, n in enumerate(
        ["bench.window", "search", "chains.block", "chains.prep", "chains.dispatch",
         "chains.wait", "chains.readback"], start=1)}
    host = [_event(1, 0, 100), _event(2, 0, 100)]
    ops = {"while.1": "jit(block)/while", "fusion.1": PATH.format("chain.price"),
           "fusion.2": PATH.format("chain.sample"), "fusion.3": PATH.format("chain.accept")}
    dev_names = {f"%{name} = f32[4]{{0}} op()": i for i, name in enumerate(ops, start=1)}
    dev_names["jit_block(7)"] = len(dev_names) + 1
    dev, modules = [], []
    for b0 in (10, 55):
        host.append(_event(3, b0, 40))
        if with_program:
            host += [_event(4, b0, 5), _event(5, b0 + 5, 1), _event(6, b0 + 6, 30),
                     _event(7, b0 + 36, 4)]
        modules.append(_event(len(dev_names), b0 + 8, 30))
        dev += [_event(1, b0 + 8, 30), _event(2, b0 + 8, 20), _event(3, b0 + 28, 6),
                _event(4, b0 + 34, 4)]
    hlo = "".join(f"\\{b:03o}" for b in _hlo_proto(ops))
    metadata = (f'event_metadata {{ key: 1 value {{ id: 1 name: "jit_block(7)" '
                f'stats {{ metadata_id: 1 bytes_value: "{hlo}" }} }} }} '
                'stat_metadata { key: 1 value { id: 1 name: "Hlo Proto" } }')
    text = " ".join([
        _plane(1, "/host:CPU", [("python", host)], host_names),
        _plane(2, "/device:TPU:0", [("XLA Modules", modules), ("XLA Ops", dev)], dev_names),
        _plane(3, "/host:metadata", [], {}, metadata)])
    from jax.profiler import ProfileData

    out = tmp_path / "plugins" / "profile" / "2026_01_01_00_00_00"
    out.mkdir(parents=True)
    (out / "host.xplane.pb").write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return str(tmp_path)


def test_the_readers_read_a_recorded_trace(tmp_path, monkeypatch):
    rec = types.SimpleNamespace(trace_dir=_write_trace(tmp_path))
    monkeypatch.setattr(drive, "_RECORDERS", [rec])
    w = _window()
    got = {name: _reader(name)(w) for name in READERS}
    assert got == pytest.approx({
        "chains.prep_ms": 5.0, "chains.readback_ms": 4.0,
        "chains.prep_idle_ms": 6.0,  # prep and dispatch both idle
        "chains.wait_idle_ms": 2.0,  # the device starts 2 ms into the wait
        "phase_sim.device_ms.chains": 20.0, "chains.moves_device_ms": 10.0})


def test_a_trace_without_the_programs_spans_reads_none(tmp_path, monkeypatch):
    rec = types.SimpleNamespace(trace_dir=_write_trace(tmp_path, with_program=False))
    monkeypatch.setattr(drive, "_RECORDERS", [rec])
    w = _window()
    for name in ("chains.prep_ms", "chains.readback_ms", "chains.prep_idle_ms",
                 "chains.wait_idle_ms"):
        assert _reader(name)(w) is None
    monkeypatch.setattr(program_trace, "program_names", lambda: None)
    for name in READERS:
        assert _reader(name)(w) is None
