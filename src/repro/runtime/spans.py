"""Named host spans and device scopes of the program.

``span(name)`` opens a ``jax.profiler.TraceAnnotation``: with a profiler
session open, the span lands on the trace's host plane, on the same clock
as the device operations; with none, it costs about a microsecond of host
time.
``span(name, stats=s, field="encode_s")`` also adds the span's wall time
(``time.perf_counter``) to the counter ``s.encode_s``, so a counter and its
span always time the same region. The profiler trace is the only sink;
the ``*Stats`` dataclasses stay the operator's counters.

Device code names its phases with ``jax.named_scope``: the scope lands in
each operation's ``op_name`` metadata, which a trace carries to the
operation that ran.

:data:`HOST_SPANS` names every host span and :data:`DEVICE_SCOPES` every
device scope; a reader of a trace takes the names from here.
"""
from __future__ import annotations

import time
from typing import Optional

import jax

# host spans, by where they are opened
HOST_SPANS = (
    # DeviceChainRunner.run_chains, one of each per (R, K) block, in order
    "chains.prep",  # encode, capacities, move table, budget row, jit lookup, fresh carry
    "chains.dispatch",  # the call of the jitted block: carry upload, enqueue (compile on a miss)
    "chains.wait",  # until the block's outputs are ready on the device
    "chains.readback",  # the copies of the outputs to the host
    # the backends (BackendStats counters)
    "backend.run_chains",  # wall_s of a chain block
    "backend.candidates",  # wall_s of a candidate batch (evaluate_candidates)
    "backend.designs",  # wall_s of PythonBackend.evaluate
    "backend.encode",  # encode_s
    "backend.dispatch",  # dispatch_s
    "backend.fetch_wait",  # fetch_wait_s: the first fetch of a batch's outputs
    "backend.decode",  # decode_s: host decode of fetched outputs
)

# device scopes of one chain step (DeviceChainRunner._build_block), then
# those of the XLA phase simulator (phase_sim_jax.simulate_one), which nest
# inside ``chain.price`` in a chain step; a reader that takes the first name
# an operation's path holds still finds ``chain.price`` there
DEVICE_SCOPES = (
    "chain.sample",  # move validity, menu logits, categorical draw
    "chain.apply",  # apply the drawn move, build the candidate's rows
    "chain.price",  # phase simulation of the candidate (kernel or XLA path)
    "chain.accept",  # SA accept and carry swap
    "phase_sim.setup",  # loop-invariant hoists: co-residency masks, one-hots, routes
    "phase_sim.phases",  # the phase loop
)


class span:
    """A host span named ``name``; with ``stats``, its wall time is added
    to ``stats.<field>`` when the block completes (not when it raises).
    A class and not a generator: it is opened once per decoded handle, and
    a ``contextlib`` generator would cost about four times the annotation."""

    __slots__ = ("_ann", "_stats", "_field", "_t0")

    def __init__(self, name: str, stats: Optional[object] = None,
                 field: Optional[str] = None):
        self._ann = jax.profiler.TraceAnnotation(name)
        self._stats, self._field = stats, field

    def __enter__(self) -> None:
        self._t0 = time.perf_counter()
        self._ann.__enter__()

    def __exit__(self, exc_type, exc, tb) -> None:
        self._ann.__exit__(exc_type, exc, tb)
        if self._stats is not None and exc_type is None:
            setattr(self._stats, self._field,
                    getattr(self._stats, self._field) + time.perf_counter() - self._t0)
