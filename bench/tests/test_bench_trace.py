"""The trace reduction: device busy time is the union of operation
intervals inside the window, idle gaps are named by the host span open in
them, and a trace recorded on the chip reads back."""
from pathlib import Path

import pytest

from bench import traces

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000  # ns


def test_busy_is_the_union_of_operations_clipped_to_the_window():
    host = [("bench.window", 10 * MS, 100 * MS),
            ("search", 10 * MS, 100 * MS),
            ("chains.block", 20 * MS, 30 * MS),
            ("chains.block", 60 * MS, 30 * MS)]
    ops = [("fusion.1", 0, 15 * MS),           # starts before the window
           ("fusion.2", 22 * MS, 20 * MS),
           ("fusion.3", 30 * MS, 5 * MS),      # overlaps fusion.2
           ("fusion.4", 45 * MS, 2 * MS),
           ("kernel", 62 * MS, 20 * MS),
           ("late", 105 * MS, 20 * MS)]        # runs past the window's end
    r = traces.reduce({"/device:TPU:0": ops}, host)
    assert r["window_s"] == pytest.approx(0.1)
    # 10-15, 22-42, 45-47, 62-82, 105-110 ms
    assert r["busy_s"] == pytest.approx(0.005 + 0.020 + 0.002 + 0.020 + 0.005)
    assert r["device_ops"][0][0] in ("fusion.2", "kernel")
    gaps = dict(r["idle_gaps"])
    # a gap goes to the innermost span open at its midpoint: 15-22, 47-62
    # and 82-105 lie mostly between the search's blocks, 42-45 inside one
    assert gaps["explorer.gap"] == pytest.approx(0.007 + 0.015 + 0.023)
    assert gaps["chains.block"] == pytest.approx(0.003)
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(r["window_s"])


def test_busy_is_averaged_over_devices():
    host = [("bench.window", 0, 10 * MS)]
    r = traces.reduce({"/device:TPU:0": [("a", 0, 10 * MS)],
                       "/device:TPU:1": [("a", 0, 5 * MS)]}, host)
    assert r["busy_s"] == pytest.approx(0.0075)


def test_a_trace_without_a_window_or_device_is_refused():
    with pytest.raises(ValueError):
        traces.reduce({}, [("bench.window", 0, 1)])
    with pytest.raises(ValueError):
        traces.reduce({"/device:TPU:0": []}, [])


def test_a_trace_file_reduces_to_its_known_busy_time():
    ev = traces.load(str(DATA / "built.xplane.pb"))
    r = traces.reduce(ev["devices"], ev["host"])
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.042)
    assert dict(r["idle_gaps"]) == pytest.approx({"explorer.gap": 0.055, "chains.block": 0.003})
    assert [n for n, _ in r["device_ops"]] == ["fusion.1", "kernel", "fusion.2"]


@pytest.mark.parametrize("path", sorted(DATA.glob("*.xplane.pb")))
def test_a_trace_file_reads_back(path):
    ev = traces.load(str(path))
    assert ev["devices"], "no TPU plane"
    r = traces.reduce(ev["devices"], ev["host"])
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["device_ops"] and r["idle_gaps"]
