"""Host spans and device scopes (``repro.runtime.spans``): every name the
program opens is listed where a trace reader finds it, a counter span
times exactly its block, a chain block opens prep → dispatch → wait →
readback once each inside the caller's span, and the device scopes are
metadata only — a chain block's outputs are bit-identical with and
without them."""
import contextlib
import dataclasses
import glob
import os
import re

import jax
import numpy as np
import pytest

from repro.core import (
    DeviceChainRunner,
    Explorer,
    ExplorerConfig,
    HardwareDatabase,
    JaxBatchedBackend,
    audio,
    calibrated_budget,
    edge_detection,
    random_single_noc_designs,
)
from repro.core.backend import Candidate
from repro.core.device_explore import ChainRequest
from repro.runtime import spans

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "src", "repro")
CHAIN_SPANS = ("chains.prep", "chains.dispatch", "chains.wait", "chains.readback")


def _names_in_source(pattern):
    found = set()
    for path in glob.glob(os.path.join(SRC, "**", "*.py"), recursive=True):
        with open(path) as f:
            found.update(re.findall(pattern, f.read()))
    return found


def test_every_span_and_scope_the_program_opens_is_listed():
    opened = _names_in_source(r'\bspan\(\s*"([^"]+)"')
    scoped = _names_in_source(r'named_scope\(\s*"([^"]+)"')
    assert opened == set(spans.HOST_SPANS)
    assert scoped == set(spans.DEVICE_SCOPES)
    assert len(set(spans.HOST_SPANS)) == len(spans.HOST_SPANS)


@dataclasses.dataclass
class _Stats:
    busy_s: float = 0.0


def test_a_counter_span_adds_its_wall_time():
    st = _Stats()
    with spans.span("backend.encode", stats=st, field="busy_s"):
        pass
    first = st.busy_s
    assert first > 0.0
    with spans.span("backend.encode", stats=st, field="busy_s"):
        sum(range(10_000))
    assert st.busy_s > first
    with pytest.raises(RuntimeError):
        with spans.span("backend.encode", stats=st, field="busy_s"):
            raise RuntimeError("boom")
    with spans.span("backend.encode"):  # no counter: a span alone
        pass


def _host_events(log_dir, names):
    """(name, start_ns, end_ns) of the host events named in ``names``."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                         "*.xplane.pb")))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in names:
                    out.append((e.name, int(e.start_ns), int(e.end_ns)))
    return sorted(out, key=lambda ev: (ev[1], -ev[2]))


def test_each_chain_block_opens_its_four_spans_in_order(tmp_path):
    db = HardwareDatabase()
    g = audio()
    bud = calibrated_budget(db)
    d = random_single_noc_designs(g, 1, seed=7)[0]
    ex = Explorer(g, db, bud, ExplorerConfig(
        policy="farsi", backend="jax", chain_r=4, chain_k=8,
        max_iterations=16, seed=3))
    ex.backend.run_chains(ChainRequest(design=d, budget=bud, r=4, k=8))  # compile
    gen = ex.run_chain_steps(d)
    pending = next(gen)
    jax.profiler.start_trace(str(tmp_path))
    try:
        while True:
            if isinstance(pending, ChainRequest):
                with jax.profiler.TraceAnnotation("chains.block"):
                    answer = [ex.backend.run_chains(pending)]
            else:
                answer = ex.backend.evaluate_candidates(pending)
            pending = gen.send(answer)
    except StopIteration:
        pass
    finally:
        jax.profiler.stop_trace()
    ev = _host_events(str(tmp_path), {"chains.block", *CHAIN_SPANS})
    blocks = [e for e in ev if e[0] == "chains.block"]
    assert len(blocks) == 2
    for _, b0, b1 in blocks:
        inside = [e for e in ev if e[0] in CHAIN_SPANS and b0 <= e[1] and e[2] <= b1]
        assert [e[0] for e in inside] == list(CHAIN_SPANS)
        for a, b in zip(inside, inside[1:]):
            assert a[2] <= b[1]  # one after the other, none nested
    chain_spans = [e for e in ev if e[0] in CHAIN_SPANS]
    assert len(chain_spans) == 2 * len(CHAIN_SPANS)  # none outside a block


def test_backend_counters_split_the_fetch_from_the_decode():
    db = HardwareDatabase()
    g, bud = edge_detection(), calibrated_budget(db)
    jb = JaxBatchedBackend(g, db)
    designs = random_single_noc_designs(g, 4, seed=3)
    handles = jb.evaluate_candidates([Candidate.of_design(x, bud) for x in designs])
    s = jb.stats()
    assert s.fetch_wait_s == 0.0 and s.decode_s == 0.0  # nothing fetched yet
    handles[0].result()
    fetched, decoded = s.fetch_wait_s, s.decode_s
    assert fetched > 0.0 and decoded > 0.0
    handles[1].result()  # the batch is on the host: decode only
    assert s.fetch_wait_s == fetched and s.decode_s > decoded
    assert s.encode_s > 0.0 and s.dispatch_s > 0.0 and s.wall_s > 0.0


def _block(g, db, d, bud, menu, alloc):
    """The outputs of one R=16 block of a fresh runner, and the compiled
    text of the block that priced it."""
    runner = DeviceChainRunner(g, db)
    seen = {}
    build = runner._block

    def spy(*a, **kw):
        fn = build(*a, **kw)

        def call(*args):
            seen["text"] = fn.lower(*args).compile().as_text()
            return fn(*args)

        return call

    runner._block = spy
    res = runner.run_chains(d, bud, r=16, k=12, seed=5, menu=menu, alloc=alloc)
    return [res.move_idx, res.accepted, res.fit_trace, *res.carry], seen["text"]


def _segment(scope):
    """``scope`` as one segment of an operation's name path, bare or as a
    transform names a scope opened inside it (``vmap(phase_sim.setup)``)."""
    return re.compile(rf"/(\w+\()?{re.escape(scope)}\)?/")


@pytest.mark.parametrize("menu,alloc", [("farsi", True), ("naive_sa", False)])
def test_scopes_leave_the_chain_block_bit_identical(monkeypatch, menu, alloc):
    """An R=16 block traced with the device scopes and one traced with
    ``jax.named_scope`` a no-op give the same arrays, bit for bit; only the
    first carries the scopes, in its operations' metadata."""
    db = HardwareDatabase()
    g = audio()
    bud = calibrated_budget(db)
    d = random_single_noc_designs(g, 1, seed=7)[0]
    scoped, with_scopes = _block(g, db, d, bud, menu, alloc)
    with monkeypatch.context() as m:
        m.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        plain, without = _block(g, db, d, bud, menu, alloc)
    assert len(scoped) == len(plain)
    for a, b in zip(scoped, plain):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    for scope in spans.DEVICE_SCOPES:
        assert _segment(scope).search(with_scopes), scope
        assert not _segment(scope).search(without), scope
    # the phase simulator's scopes nest inside the chain step's pricing
    for scope in ("phase_sim.setup", "phase_sim.phases"):
        assert f"/chain.price/vmap({scope})/" in with_scopes, scope
