"""Cross-file layout contracts: invariants that live in two (or more)
files at once and desync silently.

Each :class:`Contract` names the files that must move together, binds the
*real* objects from both sides (import or AST — never a copy of the
expected value), and diffs them. A finding always names every file
involved, because the fix is "edit these together", not "this line is
wrong".

The check logic itself is in pure functions (``check_*``) that take plain
values, so the tests can feed them deliberately-desynced inputs without
monkeypatching modules; the contract wrappers only *bind* real values and
translate messages into :class:`~repro.analysis.findings.Finding` records.

Registered contracts:

``chain-carry``    :class:`~repro.core.device_explore.ChainCarry` leaf
                   count/order vs the :class:`MoveTable` row count and the
                   per-class capacity widths ``fresh_carry`` materializes
                   — the PR-9 bug class (taboo column narrower than the
                   move table → silent modulo-aliasing of taboo TTLs) —
                   and the table's rows vs the segment layout
                   (``MoveTable.layout``) the fused block's menu is built
                   from, row count and order.
``move-codes``     the ``MV_*`` code enumeration vs ``_KIND_PRECEDENCE``
                   and the menu's per-kind validity dispatch (``_menu``)
                   — a new move kind must appear in all three.
``policy-registry`` ``POLICIES`` vs per-class ``device_menu`` eligibility
                   vs both tables in ``docs/HEURISTICS.md``.
"""
from __future__ import annotations

import ast
import dataclasses
import os
import re
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from .findings import Finding

__all__ = [
    "Contract",
    "CONTRACTS",
    "run_contracts",
    "check_chain_carry",
    "check_move_codes",
    "check_move_layout",
    "check_policy_registry",
    "parse_md_tables",
]

_REPO_SRC = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_REPO = os.path.dirname(_REPO_SRC)

F_DEVEXP = "src/repro/core/device_explore.py"
F_POLICY = "src/repro/core/policy.py"
F_HEUR = "docs/HEURISTICS.md"


@dataclasses.dataclass(frozen=True)
class Contract:
    """One cross-file invariant. ``check`` returns findings (empty = holds)."""

    name: str
    description: str
    files: Tuple[str, ...]
    check: Callable[[], List[Finding]]

    def run(self) -> List[Finding]:
        try:
            return self.check()
        except Exception as e:  # a contract that cannot even bind is a finding
            return [Finding(
                pass_name="contracts", rule=self.name,
                message=f"contract could not bind its subjects: {type(e).__name__}: {e}",
                path=self.files[0], related=self.files[1:],
            )]


def _src(rel: str) -> str:
    with open(os.path.join(_REPO, rel), "r", encoding="utf-8") as fh:
        return fh.read()


# ---------------------------------------------------------------------------
# pure checks (unit-testable with injected, deliberately-desynced values)
# ---------------------------------------------------------------------------
# the PR-8 mapping-only carry prefix: checkpoints and parity tests iterate
# these leaves positionally, so their order is load-bearing
CARRY_PREFIX = (
    "task_pe", "task_mem", "fitness", "key", "taboo", "pe_bneck", "mem_bneck",
)


def check_chain_carry(
    field_names: Sequence[str],
    taboo_width: int,
    n_moves: int,
    pe_widths: Dict[str, int],
    cap_pe: int,
    mem_widths: Dict[str, int],
    cap_mem: int,
    state_fields: Optional[Sequence[str]] = None,
) -> List[str]:
    out: List[str] = []
    if tuple(field_names[: len(CARRY_PREFIX)]) != CARRY_PREFIX:
        out.append(
            "ChainCarry's first leaves are no longer the PR-8 prefix "
            f"{CARRY_PREFIX!r} (got {tuple(field_names[:7])!r}) — "
            "checkpoints and parity tests iterate leaves positionally"
        )
    if taboo_width != n_moves:
        out.append(
            f"fresh_carry taboo width ({taboo_width}) != MoveTable.n_moves "
            f"({n_moves}) — taboo TTLs silently alias across move rows "
            "(the PR-9 desync)"
        )
    for col, w in pe_widths.items():
        if w != cap_pe:
            out.append(
                f"carry.{col} width ({w}) != cap_pe ({cap_pe}) — the "
                "fused block scatters by slot index into this column"
            )
    for col, w in mem_widths.items():
        if w != cap_mem:
            out.append(
                f"carry.{col} width ({w}) != cap_mem ({cap_mem})"
            )
    if state_fields is not None:
        state = tuple(state_fields)
        expect = tuple(
            f for f in field_names
            if f not in ("fitness", "key", "taboo", "pe_bneck", "mem_bneck")
        )
        if state != expect:
            missing = [f for f in expect if f not in state]
            extra = [f for f in state if f not in expect]
            out.append(
                "_build_block._STATE does not cover the carry's swap-on-"
                f"accept leaves (missing {missing!r}, extra {extra!r}) — "
                "an uncovered leaf keeps its rejected value after an accept"
            )
    return out


def check_move_layout(
    segments: Sequence[Tuple[int, int, int]],
    kind: Sequence[int],
    arg: Sequence[int],
    dest: Sequence[int],
    menu_width: int,
) -> List[str]:
    """The packed table's rows are the layout's ``(kind, n_arg, n_dest)``
    segments expanded in order (row ``a·n_dest + d`` of a segment has
    ``arg = a``, ``dest = d``), and the menu built from that layout has one
    column per row."""
    out: List[str] = []
    kind, arg, dest = list(kind), list(arg), list(dest)
    off = 0
    for k, n_a, n_d in segments:
        n = n_a * n_d
        want = (
            [k] * n,
            [a for a in range(n_a) for _ in range(n_d)],
            list(range(n_d)) * n_a,
        )
        got = (kind[off:off + n], arg[off:off + n], dest[off:off + n])
        if tuple(got) != want:
            out.append(
                f"MoveTable rows {off}..{off + n - 1} are not the layout's "
                f"segment (kind {k}, {n_a} × {n_d}) — the fused block's "
                "menu would score those rows as other moves"
            )
        off += n
    if off != len(kind):
        out.append(
            f"MoveTable.layout covers {off} rows, the table has {len(kind)}"
        )
    if menu_width != len(kind):
        out.append(
            f"the fused block's menu has {menu_width} columns for "
            f"{len(kind)} move-table rows — sampled row numbers would not "
            "name table rows"
        )
    return out


def check_move_codes(
    codes: Dict[str, int],
    precedence_len: int,
    dispatch_names: Sequence[str],
) -> List[str]:
    out: List[str] = []
    vals = sorted(codes.values())
    if vals != list(range(len(codes))):
        out.append(
            f"MV_* codes are not a dense 0..{len(codes) - 1} enumeration: "
            f"{dict(sorted(codes.items(), key=lambda kv: kv[1]))!r} — the "
            "kind column indexes _KIND_PRECEDENCE positionally"
        )
    for name, v in codes.items():
        want_suffix = "_PE" if v % 2 == 0 else "_MEM"
        if not name.endswith(want_suffix):
            out.append(
                f"{name}={v} breaks the even=PE / odd=MEM convention the "
                "validity mask and apply_move scatter classes rely on"
            )
    if precedence_len != len(codes):
        out.append(
            f"_KIND_PRECEDENCE has {precedence_len} entries for "
            f"{len(codes)} MV_* codes — the farsi menu would read a "
            "precedence off the end (or miss a kind)"
        )
    missing = sorted(set(codes) - set(dispatch_names))
    if missing:
        out.append(
            f"the fused block's menu (`_menu`) never tests {missing!r}"
            " — rows of that kind are unconditionally invalid (dead moves)"
        )
    return out


def check_policy_registry(
    policy_menus: Dict[str, str],
    menus: Sequence[str],
    doc_menu_rows: Dict[str, str],
    doc_listed_policies: Sequence[str],
) -> List[str]:
    out: List[str] = []
    for name, menu in sorted(policy_menus.items()):
        if menu not in menus:
            out.append(
                f"POLICIES[{name!r}].device_menu = {menu!r} is not in "
                f"device_explore.MENUS {tuple(menus)!r}"
            )
        doc = doc_menu_rows.get(name)
        if doc is None:
            out.append(
                f"policy {name!r} is missing from the device-eligibility "
                "table in docs/HEURISTICS.md"
            )
        elif doc != menu:
            out.append(
                f"docs/HEURISTICS.md says {name!r} uses menu {doc!r} but "
                f"the class declares device_menu={menu!r}"
            )
    listed = set(doc_listed_policies)
    for name in sorted(policy_menus):
        if name not in listed:
            out.append(
                f"policy {name!r} is registered but absent from the "
                "'Registered policies' table in docs/HEURISTICS.md"
            )
    for name in sorted(listed - set(policy_menus)):
        out.append(
            f"docs/HEURISTICS.md lists policy {name!r} which is not in "
            "POLICIES"
        )
    for name in sorted(set(doc_menu_rows) - set(policy_menus)):
        out.append(
            f"device-eligibility table names unknown policy {name!r}"
        )
    return out


# ---------------------------------------------------------------------------
# markdown table parsing (docs/HEURISTICS.md is a contract subject)
# ---------------------------------------------------------------------------
def parse_md_tables(text: str) -> List[List[List[str]]]:
    """All pipe-tables in a markdown document as lists of rows of cell
    strings (header row included, separator rows dropped)."""
    tables: List[List[List[str]]] = []
    cur: List[List[str]] = []
    for line in text.splitlines():
        s = line.strip()
        if s.startswith("|") and s.endswith("|"):
            cells = [c.strip() for c in s[1:-1].split("|")]
            if all(re.fullmatch(r":?-{3,}:?", c) for c in cells):
                continue
            cur.append(cells)
        else:
            if cur:
                tables.append(cur)
                cur = []
    if cur:
        tables.append(cur)
    return tables


def _ticked(cell: str) -> List[str]:
    return re.findall(r"`([^`]+)`", cell)


def _heuristics_doc_bindings(text: str) -> Tuple[Dict[str, str], List[str]]:
    """(policy → documented menu) from the device-eligibility table, and
    the policy names listed in the registered-policies table."""
    menu_rows: Dict[str, str] = {}
    listed: List[str] = []
    for table in parse_md_tables(text):
        header = [c.lower() for c in table[0]]
        if header[:2] == ["name", "selection"]:
            for row in table[1:]:
                listed.extend(_ticked(row[0]))
        elif header[0] == "policy" and "device_menu" in header[1]:
            for row in table[1:]:
                menus = _ticked(row[1])
                menu = menus[0] if menus else ""
                for name in _ticked(row[0]):
                    menu_rows[name] = menu
    return menu_rows, listed


# ---------------------------------------------------------------------------
# AST extraction helpers (the side of a contract that is *code shape*)
# ---------------------------------------------------------------------------
def _find_func(tree: ast.Module, name: str) -> Optional[ast.FunctionDef]:
    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef) and node.name == name:
            return node
    return None


def dispatch_mv_names(src: str) -> List[str]:
    """Every ``MV_*`` name the fused block's menu (``_menu``, which builds
    each kind's validity block) refers to."""
    fn = _find_func(ast.parse(src), "_menu")
    if fn is None:
        return []
    return sorted({
        sub.id for sub in ast.walk(fn)
        if isinstance(sub, ast.Name) and sub.id.startswith("MV_")
    })


def state_tuple_fields(src: str) -> Optional[List[str]]:
    """The ``_STATE`` tuple literal inside ``_build_block`` — the carry
    leaves the accept step swaps wholesale."""
    tree = ast.parse(src)
    fn = _find_func(tree, "_build_block")
    if fn is None:
        return None
    for node in ast.walk(fn):
        if isinstance(node, ast.Assign) and any(
            isinstance(t, ast.Name) and t.id == "_STATE" for t in node.targets
        ):
            if isinstance(node.value, (ast.Tuple, ast.List)):
                return [
                    e.value for e in node.value.elts
                    if isinstance(e, ast.Constant) and isinstance(e.value, str)
                ]
    return None


# ---------------------------------------------------------------------------
# contract bindings (real imports / real fixtures)
# ---------------------------------------------------------------------------
def _msgs_to_findings(
    msgs: List[str], rule: str, path: str, related: Tuple[str, ...]
) -> List[Finding]:
    return [
        Finding(pass_name="contracts", rule=rule, message=m,
                path=path, related=related)
        for m in msgs
    ]


def _carry_fixture():
    """Smallest real binding: the audio workload on a random single-NoC
    design, alloc menu over deliberately non-pow2 capacities (a pow2
    assumption hiding in a width computation must not pass by luck)."""
    from repro.core import (
        DeviceChainRunner, HardwareDatabase, audio, random_single_noc_designs,
    )
    from repro.core.phase_sim_jax import EncodedDesign

    db = HardwareDatabase()
    g = audio()
    d = random_single_noc_designs(g, 1, seed=7)[0]
    runner = DeviceChainRunner(g, db)
    ed = EncodedDesign.of(d, g, db, runner.enc)
    cap_pe = int(ed.pe_peak.shape[0]) + 3
    cap_mem = int(ed.mem_bw.shape[0]) + 2
    return runner, d, ed, cap_pe, cap_mem


def _menu_width(carry, segments) -> int:
    """Columns of the menu the fused block builds over ``segments`` for
    ``carry``, traced abstractly."""
    import jax
    import jax.numpy as jnp

    from repro.core.device_explore import _KIND_PRECEDENCE, ChainCarry, _menu

    prec_log = jnp.log(jnp.asarray(_KIND_PRECEDENCE))
    valid, _ = jax.eval_shape(
        lambda c: _menu(ChainCarry(*c), segments, "farsi", prec_log),
        tuple(carry),
    )
    return int(valid.shape[1])


def _check_carry() -> List[Finding]:
    from repro.core.device_explore import ChainCarry, MoveTable

    runner, d, ed, cap_pe, cap_mem = _carry_fixture()
    table = MoveTable.of(
        ed, runner.enc, alloc=True, cap_pe=cap_pe, cap_mem=cap_mem
    )
    carry = runner.fresh_carry(
        d, ed, r=2, seed=0, cap_pe=cap_pe, cap_mem=cap_mem, alloc=True
    )
    pe_cols = ("pe_bneck", "pe_active", "pe_peak", "pe_pj", "pe_leak",
               "pe_area", "pe_noc", "pe_rung", "pe_src")
    mem_cols = ("mem_bneck", "mem_active", "mem_bw", "mem_pj", "mem_leak",
                "mem_area_fixed", "mem_area_per_mb", "mem_noc", "mem_rung",
                "mem_src")
    msgs = check_chain_carry(
        field_names=ChainCarry._fields,
        taboo_width=int(carry.taboo.shape[1]),
        n_moves=table.n_moves,
        pe_widths={c: int(getattr(carry, c).shape[1]) for c in pe_cols},
        cap_pe=cap_pe,
        mem_widths={c: int(getattr(carry, c).shape[1]) for c in mem_cols},
        cap_mem=cap_mem,
        state_fields=state_tuple_fields(_src(F_DEVEXP)),
    )
    if len(carry) != len(ChainCarry._fields):
        msgs.append(
            f"fresh_carry returned {len(carry)} leaves for a "
            f"{len(ChainCarry._fields)}-field ChainCarry"
        )
    t = len(runner.enc.names)
    # the layout _build_block derives from the block's static sizes
    segments = MoveTable.layout(
        t, cap_pe, cap_mem, int(ed.noc_bw.shape[0]), True
    )
    msgs.extend(check_move_layout(
        segments, table.kind, table.task, table.dest,
        _menu_width(carry, segments),
    ))
    if tuple(carry.accel.shape) != (2, t, cap_pe):
        msgs.append(
            f"carry.accel shape {tuple(carry.accel.shape)} != (R, T, "
            f"cap_pe) = (2, {t}, {cap_pe})"
        )
    return _msgs_to_findings(msgs, "chain-carry", F_DEVEXP, ())


def _check_moves() -> List[Finding]:
    from repro.core import device_explore as dx

    codes = {
        n: int(getattr(dx, n))
        for n in dir(dx)
        if n.startswith("MV_") and isinstance(getattr(dx, n), int)
    }
    msgs = check_move_codes(
        codes=codes,
        precedence_len=int(dx._KIND_PRECEDENCE.shape[0]),
        dispatch_names=dispatch_mv_names(_src(F_DEVEXP)),
    )
    return _msgs_to_findings(msgs, "move-codes", F_DEVEXP, ())


def _check_policies() -> List[Finding]:
    from repro.core.device_explore import MENUS
    from repro.core.policy import POLICIES

    doc_menus, doc_listed = _heuristics_doc_bindings(_src(F_HEUR))
    msgs = check_policy_registry(
        policy_menus={n: cls.device_menu for n, cls in POLICIES.items()},
        menus=MENUS,
        doc_menu_rows=doc_menus,
        doc_listed_policies=doc_listed,
    )
    return _msgs_to_findings(msgs, "policy-registry", F_POLICY, (F_HEUR, F_DEVEXP))


CONTRACTS: Tuple[Contract, ...] = (
    Contract(
        name="chain-carry",
        description="ChainCarry leaves ↔ MoveTable row count ↔ fresh_carry "
        "widths ↔ _build_block._STATE coverage (PR-9 taboo-width class) ↔ "
        "MoveTable.layout segments the menu is built from",
        files=(F_DEVEXP,),
        check=_check_carry,
    ),
    Contract(
        name="move-codes",
        description="MV_* enumeration ↔ _KIND_PRECEDENCE ↔ the menu's "
        "per-kind validity dispatch",
        files=(F_DEVEXP,),
        check=_check_moves,
    ),
    Contract(
        name="policy-registry",
        description="POLICIES ↔ device_menu eligibility ↔ both "
        "docs/HEURISTICS.md tables",
        files=(F_POLICY, F_HEUR, F_DEVEXP),
        check=_check_policies,
    ),
)


def run_contracts(
    names: Optional[Sequence[str]] = None,
) -> List[Finding]:
    """Run the registry (or the named subset) and return all findings."""
    out: List[Finding] = []
    for c in CONTRACTS:
        if names is not None and c.name not in names:
            continue
        out.extend(c.run())
    return out
