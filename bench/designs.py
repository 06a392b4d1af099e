"""Designs in and out of the program, as plain data.

:func:`snapshot` reads a design the program holds into the plain dict the
reference prices (``bench/reference.py``); :func:`decode_chain` reads one
chain of a device chain block the same way, from the block's input design
and its output carry. :func:`seeded_platform` builds a search's starting
platform from a seed through the program's own design API, the way
``chip_smoke.py``'s multi-NoC builder does, but with a fixed inventory so
every search of a cell compiles to one shape.
"""
from __future__ import annotations

import random
from typing import List

# Table 3's frequency ladder (MHz): the rung a device swap move steps along
FREQ_LADDER_MHZ = (100, 200, 300, 400, 500, 600, 700, 800)

_FIELDS = ("subtype", "freq_mhz", "width_bytes", "n_links", "unroll", "hardened_for")


def snapshot(design) -> dict:
    """The program design as plain data (blocks in insertion order)."""
    blocks = []
    for name, b in design.blocks.items():
        d = {"name": name, "kind": b.kind.value}
        d.update({f: getattr(b, f) for f in _FIELDS})
        blocks.append(d)
    return {
        "blocks": blocks,
        "noc_chain": list(design.noc_chain),
        "attached": dict(design.attached_noc),
        "task_pe": dict(design.task_pe),
        "task_mem": dict(design.task_mem),
    }


def decode_chain(base: dict, carry, chain: int, task_names: List[str]) -> dict:
    """Chain ``chain``'s design after a block: slot j of a class is the
    base design's j-th block of that class (insertion order); an active
    slot is a copy of the base block it was forked from (``*_src``), at the
    frequency rung ``*_rung``, attached at chain position ``*_noc``; tasks
    map to slots by ``task_pe``/``task_mem``. A mapping-only block keeps
    every slot where it was, and decodes the same way."""
    out_blocks = [b for b in base["blocks"] if b["kind"] == "noc"]
    attached = {}
    names = {}
    for kind, prefix in (("pe", "pe"), ("mem", "mem")):
        base_slots = [b for b in base["blocks"] if b["kind"] == kind]
        active = getattr(carry, f"{prefix}_active")[chain]
        src = getattr(carry, f"{prefix}_src")[chain]
        rung = getattr(carry, f"{prefix}_rung")[chain]
        noc = getattr(carry, f"{prefix}_noc")[chain]
        for j in range(len(active)):
            if active[j] <= 0.5:
                continue
            origin = base_slots[int(src[j])]
            blk = dict(origin)
            blk["name"] = f"{kind}{j}"
            blk["freq_mhz"] = FREQ_LADDER_MHZ[int(rung[j])]
            out_blocks.append(blk)
            attached[blk["name"]] = base["noc_chain"][int(noc[j])]
            names[(kind, j)] = blk["name"]
    task_pe = {t: names[("pe", int(carry.task_pe[chain][i]))]
               for i, t in enumerate(task_names)}
    task_mem = {t: names[("mem", int(carry.task_mem[chain][i]))]
                for i, t in enumerate(task_names)}
    return {
        "blocks": out_blocks,
        "noc_chain": list(base["noc_chain"]),
        "attached": attached,
        "task_pe": task_pe,
        "task_mem": task_mem,
    }


def seeded_platform(g, rng: random.Random, accelerators: int, memories: int,
                    nocs: int):
    """A starting platform: the base design (one GPP, one DRAM, one NoC)
    plus ``accelerators`` accelerators hardened for distinct tasks drawn
    from ``rng`` and ``memories`` memories of drawn kind and frequency,
    with the NoC forked into a chain of ``nocs``, then every task and
    buffer mapped at random. Only the draws vary with the seed; the
    inventory, and so the compiled shape, does not."""
    from repro.core import Design, make_accelerator, make_mem
    from repro.core.moves import apply_fork

    tasks = sorted(g.tasks)
    d = Design.base(g)
    noc0 = d.noc_chain[0]
    for t in rng.sample(tasks, accelerators):
        d.add_block(make_accelerator(t, rng.choice((100, 400))), attach_to=noc0)
    for _ in range(memories):
        d.add_block(make_mem(rng.choice(("dram", "sram")), rng.choice((100, 800)), 32),
                    attach_to=noc0)
    while len(d.noc_chain) < nocs:
        forkable = [n for n in d.noc_chain if len(d.attached(n)) >= 2]
        if not apply_fork(d, g, rng.choice(forkable)):
            raise RuntimeError("NoC fork refused")
    pes, mems = d.pes(), d.mems()
    for t in tasks:
        d.task_pe[t] = rng.choice(pes)
        d.task_mem[t] = rng.choice(mems)
    return d
