"""The phase simulator's device scopes in a trace: leaf device time under
``chain.price`` and ``phase_sim.setup`` or ``phase_sim.phases`` goes to
their readers, ``phase_sim.device_ms.chains`` reads the same total with and
without them, and each reader returns None where it has nothing to read."""
import types

import pytest

from bench import drive, program_trace
from bench.tests.test_bench_program_trace import (_event, _hlo_proto, _plane, _reader,
                                                   _window)

READERS = ("phase_sim.setup_ms.chains", "phase_sim.phases_ms.chains")
PRICE = "jit(block)/while/body/closed_call/chain.price"
# op name paths as a chain step's XLA pricing names them: the simulator runs
# under vmap, which wraps the first scope opened inside it
INNER = {
    "fusion.1": f"{PRICE}/vmap(phase_sim.setup)/eq",
    "fusion.2": f"{PRICE}/vmap(phase_sim.phases)/while/body/dot_general",
    "fusion.3": f"{PRICE}/segment_max",  # the rollup after the loop
    "fusion.4": "jit(block)/while/body/closed_call/chain.sample/gather",
    # a candidate priced outside a chain step
    "fusion.5": "jit(simulate_batch)/vmap(phase_sim.phases)/while/body/dot_general",
}
# the same program without the simulator's scopes
OUTER = {k: v.replace("/vmap(phase_sim.setup)", "").replace("/vmap(phase_sim.phases)", "")
         for k, v in INNER.items()}


def _write_trace(tmp_path, paths):
    """Two chain blocks of 40 ms in a 100 ms window; in each, the device
    runs a 30 ms while holding setup 4 ms, phases 16 ms, rollup 2 ms,
    moves 6 ms, and then 1 ms of pricing outside the block."""
    host_names = {"bench.window": 1, "search": 2, "chains.block": 3, "chains.wait": 4}
    ops = {"while.1": "jit(block)/while", **paths}
    dev_names = {f"%{name} = f32[4]{{0}} op()": i for i, name in enumerate(ops, start=1)}
    dev_names["jit_block(7)"] = len(dev_names) + 1
    host, dev, modules = [_event(1, 0, 100), _event(2, 0, 100)], [], []
    for b0 in (10, 55):
        host += [_event(3, b0, 40), _event(4, b0 + 6, 30)]
        modules.append(_event(len(dev_names), b0 + 8, 31))
        dev += [_event(1, b0 + 8, 30), _event(2, b0 + 8, 4), _event(3, b0 + 12, 16),
                _event(4, b0 + 28, 2), _event(5, b0 + 30, 6), _event(6, b0 + 38, 1)]
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


def _read(tmp_path, monkeypatch, paths, names):
    rec = types.SimpleNamespace(trace_dir=_write_trace(tmp_path, paths))
    monkeypatch.setattr(drive, "_RECORDERS", [rec])
    w = _window()
    return {name: _reader(name)(w) for name in names}


def test_the_readers_split_the_pricing_into_setup_and_phases(tmp_path, monkeypatch):
    got = _read(tmp_path, monkeypatch, INNER, (*READERS, "phase_sim.device_ms.chains"))
    assert got == pytest.approx({"phase_sim.setup_ms.chains": 4.0,
                                 "phase_sim.phases_ms.chains": 16.0,
                                 "phase_sim.device_ms.chains": 22.0})


def test_the_pricing_reads_the_same_with_and_without_the_inner_scopes(tmp_path, monkeypatch):
    with_inner = _read(tmp_path / "a", monkeypatch, INNER, ("phase_sim.device_ms.chains",
                                                            "chains.moves_device_ms"))
    without = _read(tmp_path / "b", monkeypatch, OUTER, ("phase_sim.device_ms.chains",
                                                         "chains.moves_device_ms"))
    assert with_inner == without == pytest.approx({"phase_sim.device_ms.chains": 22.0,
                                                   "chains.moves_device_ms": 6.0})


def test_a_program_without_the_inner_scopes_reads_none(tmp_path, monkeypatch):
    got = _read(tmp_path, monkeypatch, OUTER, READERS)
    assert got == {name: None for name in READERS}
    monkeypatch.setattr(program_trace, "program_names", lambda: None)
    assert _reader(READERS[0])(_window()) is None


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
    assert read(_window(blocks=0)) is None
