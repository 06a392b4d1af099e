"""Regenerate tests/golden_chain_seqs.json.

The fixture pins what a fused (R, K) device chain block does: every chain's
sampled move-table rows (``move_idx``), its accept/reject flags and its
incumbent fitness after each step (``fit_trace``, as float32 bit patterns),
for R=4, K=8 on three deployments and every device menu:

* ``audio.mapping`` — the Audio graph, mapping-only table, on a seeded
  5-PE 3-memory 2-NoC platform;
* ``audio.alloc`` — the Audio graph, mixed mapping+allocation table, on a
  random single-NoC design;
* ``pulse_doppler.alloc`` — the pulse-Doppler graph at 8 pulses, 32 range
  bins and 4 Doppler tasks (29 tasks), mixed table on a seeded 5-PE
  3-memory 2-NoC platform, so NoC-attach rows are live.

`tests/test_move_menu.py::test_chain_block_replays_golden_sequences` replays
every entry bit for bit. Run me from the repo root
(``PYTHONPATH=src python tests/gen_golden_chain_seqs.py``) ONLY when search
behaviour changes deliberately, and say so in the commit. It runs on the
CPU and imports only the public chain API, so it can record any commit.
"""
import json
import os
import random
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "golden_chain_seqs.json")
MENUS = ("naive_sa", "telemetry", "farsi")
R, K, SEED = 4, 8, 5


def deployment(name):
    """(graph, database, budget, design, alloc) of one fixture deployment."""
    from bench.designs import seeded_platform
    from repro.core import (
        HardwareDatabase, audio, calibrated_budget, pulse_doppler,
        random_single_noc_designs,
    )
    from repro.core.workloads import synthetic_budget

    db = HardwareDatabase()
    if name == "audio.mapping":
        g = audio()
        return g, db, calibrated_budget(db), \
            seeded_platform(g, random.Random(11), 4, 2, 2), False
    if name == "audio.alloc":
        g = audio()
        return g, db, calibrated_budget(db), \
            random_single_noc_designs(g, 1, seed=7)[0], True
    if name == "pulse_doppler.alloc":
        g = pulse_doppler(pulses=8, range_bins=32, doppler_tasks=4)
        return g, db, synthetic_budget(g, db), \
            seeded_platform(g, random.Random(2**31 + 11), 4, 2, 2), True
    raise KeyError(name)


DEPLOYMENTS = ("audio.mapping", "audio.alloc", "pulse_doppler.alloc")


def record(name, menu):
    """One fixture entry: the block's traces, as plain lists."""
    from repro.core import DeviceChainRunner

    g, db, bud, d, alloc = deployment(name)
    res = DeviceChainRunner(g, db).run_chains(
        d, bud, r=R, k=K, seed=SEED, menu=menu, alloc=alloc
    )
    return {
        "n_moves": int(res.n_moves),
        "move_idx": np.asarray(res.move_idx).tolist(),
        "accepted": np.asarray(res.accepted).astype(int).tolist(),
        "fit_trace_bits": np.asarray(res.fit_trace, np.float32)
        .view(np.uint32).tolist(),
    }


def main() -> None:
    out = {
        f"{name}.{menu}": record(name, menu)
        for name in DEPLOYMENTS for menu in MENUS
    }
    with open(GOLDEN, "w", encoding="utf-8") as fh:
        json.dump(out, fh, indent=None, separators=(",", ":"))
        fh.write("\n")
    print(f"wrote {len(out)} entries to {GOLDEN}")


if __name__ == "__main__":
    main()
