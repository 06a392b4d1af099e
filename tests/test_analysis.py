"""repro.analysis acceptance pins.

Every pass must (a) exit clean on the shipped tree and (b) demonstrably
fail on a seeded mutation of the exact bug class it was built for:
reordering a SCAL_COLS entry and narrowing the ChainCarry taboo column
(the PR-9 desync) must trip the contract checker, and a ``float()`` host
sync injected into the fused chain scan must trip the lint. The lint
rules are pinned per-rule with trigger / no-trigger fixture snippets so a
rule that rots (stops firing, or starts firing on the legal idiom) fails
here, not in review.
"""
import json
import os
import subprocess
import sys

from repro.analysis.contracts import (
    CARRY_PREFIX,
    check_chain_carry,
    check_move_codes,
    check_move_layout,
    check_policy_registry,
    check_rollup_anchors,
    check_scal_cols,
    dispatch_mv_names,
    kernel_rollup_sources,
    kernel_rollup_width,
    parse_md_tables,
    state_tuple_fields,
)
from repro.analysis.findings import Finding
from repro.analysis.lint import (
    apply_baseline,
    lint_source,
    write_baseline,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEVEXP = os.path.join(REPO, "src", "repro", "core", "device_explore.py")


def _live(findings, rule=None):
    return [
        f for f in findings
        if f.live and (rule is None or f.rule == rule)
    ]


# ---------------------------------------------------------------------------
# lint rules: one trigger and one no-trigger snippet each
# ---------------------------------------------------------------------------
def test_lint_host_sync_float_in_jitted_fn():
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return float(x) + 1\n"
    )
    assert _live(lint_source(src), "host-sync")


def test_lint_host_sync_item_and_asarray():
    src = (
        "import jax, numpy as np\n"
        "def step(c, x):\n"
        "    v = c.item()\n"
        "    w = np.asarray(x)\n"
        "    return c, w\n"
        "def run(xs):\n"
        "    import jax.lax as lax\n"
        "    return lax.scan(step, 0, xs)\n"
    )
    hits = _live(lint_source(src), "host-sync")
    assert len(hits) == 2, hits


def test_lint_host_sync_not_outside_traced_scope():
    src = (
        "def host_only(x):\n"
        "    return float(x)\n"
    )
    assert not _live(lint_source(src))


def test_lint_host_sync_float_on_literal_ok():
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return x * float('inf')\n"
    )
    assert not _live(lint_source(src), "host-sync")


def test_lint_tracer_branch_flags_jnp_call_test():
    src = (
        "import jax, jax.numpy as jnp\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    if jnp.sum(x) > 0:\n"
        "        return x\n"
        "    return -x\n"
    )
    assert _live(lint_source(src), "tracer-branch")


def test_lint_tracer_branch_static_config_ok():
    # static-config branches and dtype comparisons are the shipped idiom
    # (backend.packed() selects columns by dtype at trace time)
    src = (
        "import jax, jax.numpy as jnp\n"
        "@jax.jit\n"
        "def f(x, n_noc=1, menu='farsi'):\n"
        "    if n_noc == 1:\n"
        "        x = x + 1\n"
        "    if menu in ('farsi', 'telemetry'):\n"
        "        x = x * 2\n"
        "    y = x if x.dtype == jnp.float32 else x.astype(jnp.float32)\n"
        "    return y\n"
    )
    assert not _live(lint_source(src))


def test_lint_f64_promote_math_call():
    src = (
        "import jax, math\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return math.exp(x)\n"
    )
    assert _live(lint_source(src), "f64-promote")


def test_lint_f64_promote_dtype_kw():
    src = (
        "import jax, jax.numpy as jnp\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return jnp.zeros((3,), dtype='float64') + x\n"
    )
    assert _live(lint_source(src), "f64-promote")


def test_lint_mutable_closure_append():
    src = (
        "import jax\n"
        "acc = []\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    acc.append(x)\n"
        "    return x\n"
    )
    assert _live(lint_source(src), "mutable-closure")


def test_lint_mutable_closure_local_list_ok():
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    acc = []\n"
        "    acc.append(x)\n"
        "    return x\n"
    )
    assert not _live(lint_source(src), "mutable-closure")


def test_lint_mutable_closure_pallas_ref_write_ok():
    # `o_ref[...] = acc` on a closed-over Ref is THE Pallas output idiom
    src = (
        "def kernel(x_ref, o_ref):\n"
        "    def body(i):\n"
        "        o_ref[i] = x_ref[i] * 2\n"
        "    import jax.lax as lax\n"
        "    lax.fori_loop(0, 4, lambda i, _: body(i), None)\n"
        "def call(x):\n"
        "    import jax.experimental.pallas as pl\n"
        "    return pl.pallas_call(kernel)(x)\n"
    )
    assert not _live(lint_source(src), "mutable-closure")


def test_lint_traced_marker_comment():
    # cross-module entry points carry `# repro: traced` — no visible jit
    src = (
        "def hot(x):  # repro: traced\n"
        "    return float(x)\n"
    )
    assert _live(lint_source(src), "host-sync")


def test_lint_vmap_lambda_marks_callee():
    # the shipped simulate_batch shape: vmap over a lambda that calls a
    # same-module def — the callee must inherit the traced scope
    src = (
        "import jax\n"
        "def simulate_one(row):\n"
        "    return float(row)\n"
        "def simulate_batch(rows):\n"
        "    return jax.vmap(lambda r: simulate_one(r))(rows)\n"
    )
    assert _live(lint_source(src), "host-sync")


# ---------------------------------------------------------------------------
# suppression + baseline machinery
# ---------------------------------------------------------------------------
def test_noqa_suppresses_with_reason():
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return float(x)  # repro: noqa[host-sync]: proven static here\n"
    )
    fs = lint_source(src)
    assert not _live(fs)
    assert any(f.suppressed for f in fs)


def test_noqa_without_reason_is_its_own_finding():
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return float(x)  # repro: noqa[host-sync]\n"
    )
    assert _live(lint_source(src), "noqa-reason")


def test_noqa_wrong_rule_does_not_suppress():
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return float(x)  # repro: noqa[f64-promote]: wrong rule\n"
    )
    assert _live(lint_source(src), "host-sync")


def test_baseline_roundtrip(tmp_path):
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return float(x)\n"
    )
    findings = lint_source(src, path="src/repro/x.py")
    p = tmp_path / "baseline.json"
    write_baseline(findings, str(p))
    base = {
        k: v for k, v in json.loads(p.read_text())["findings"].items()
    }
    after = apply_baseline(lint_source(src, path="src/repro/x.py"), base)
    assert not _live(after)
    assert any(f.baselined for f in after)
    # a NEW finding in the same file stays live
    src2 = src + "\n@jax.jit\ndef g(y):\n    return float(y)\n"
    after2 = apply_baseline(lint_source(src2, path="src/repro/x.py"), base)
    assert _live(after2)


def test_shipped_baseline_is_empty_for_core():
    """Satellite pin: the frozen lint debt must stay empty for
    src/repro/core/ (it is empty for the whole tree today)."""
    with open(os.path.join(
        REPO, "src", "repro", "analysis", "baseline.json"
    )) as fh:
        frozen = json.load(fh)["findings"]
    assert not {
        k: v for k, v in frozen.items() if k.startswith("src/repro/core/")
    }, frozen


# ---------------------------------------------------------------------------
# contracts: pure checks on deliberately-desynced inputs
# ---------------------------------------------------------------------------
_COLS = (
    "latency_s", "energy_j", "power_w", "area_mm2", "fitness",
    "alp_time_s", "traffic_bytes", "n_phases", "all_done",
    "kind_pe_s", "kind_mem_s", "kind_noc_s", "top_bneck_pe", "top_bneck_mem",
)


def test_contract_scal_cols_clean():
    assert not check_scal_cols(_COLS, _COLS, _COLS[:9], 14, 14)


def test_contract_scal_cols_reorder_trips():
    """Acceptance mutation 1: swapping two SCAL_COLS entries on one side
    must produce a finding."""
    swapped = list(_COLS)
    swapped[4], swapped[5] = swapped[5], swapped[4]
    assert check_scal_cols(_COLS, tuple(swapped), _COLS[:9], 14, 14)


def test_contract_scal_cols_single_source_reorder_trips():
    """Acceptance mutation 1, hardened: because every module imports the
    schema from core.scal_layout, a reorder of the single source passes
    the name-diff tautologically — the rollup ANCHORS must catch it
    against the kernel's positional stack."""
    with open(os.path.join(
        REPO, "src", "repro", "kernels", "phase_sim", "kernel.py"
    ), encoding="utf-8") as fh:
        rollup = kernel_rollup_sources(fh.read())
    assert rollup is not None and len(rollup) == 14
    assert not check_rollup_anchors(_COLS, rollup)  # shipped order holds
    swapped = list(_COLS)
    swapped[0], swapped[1] = swapped[1], swapped[0]  # latency_s ↔ energy_j
    assert check_rollup_anchors(tuple(swapped), rollup)
    swapped2 = list(_COLS)
    swapped2[4], swapped2[6] = swapped2[6], swapped2[4]  # fitness ↔ traffic
    assert check_rollup_anchors(tuple(swapped2), rollup)


def test_contract_scal_cols_width_drift_trips():
    assert check_scal_cols(_COLS, _COLS, _COLS[:9], 13, 14)
    assert check_scal_cols(_COLS, _COLS, _COLS[:9], 14, 13)


def test_contract_chain_carry_taboo_narrowed_trips():
    """Acceptance mutation 2 (the PR-9 regression shape): a taboo column
    one row narrower than the move table must produce a finding."""
    fields = CARRY_PREFIX + ("pe_active",)
    ok = check_chain_carry(fields, 120, 120, {"pe_active": 8}, 8, {}, 4)
    assert not ok
    bad = check_chain_carry(fields, 119, 120, {"pe_active": 8}, 8, {}, 4)
    assert bad and any("PR-9" in m for m in bad)


def test_contract_chain_carry_prefix_order_trips():
    fields = ("task_mem", "task_pe") + CARRY_PREFIX[2:]
    assert check_chain_carry(fields, 10, 10, {}, 4, {}, 4)


def test_contract_chain_carry_state_coverage_trips():
    fields = CARRY_PREFIX + ("pe_active", "accel")
    ok = check_chain_carry(
        fields, 10, 10, {}, 4, {}, 4,
        state_fields=("task_pe", "task_mem", "pe_active", "accel"),
    )
    assert not ok
    bad = check_chain_carry(
        fields, 10, 10, {}, 4, {}, 4,
        state_fields=("task_pe", "task_mem", "pe_active"),  # accel dropped
    )
    assert bad and any("accel" in m for m in bad)


def test_contract_move_codes_clean_and_trips():
    codes = {
        "MV_MIG_PE": 0, "MV_MIG_MEM": 1, "MV_FORK_PE": 2, "MV_FORK_MEM": 3,
    }
    assert not check_move_codes(codes, 4, list(codes))
    # sparse enumeration
    assert check_move_codes({**codes, "MV_FORK_MEM": 5}, 4, list(codes))
    # parity convention
    assert check_move_codes(
        {"MV_MIG_PE": 1, "MV_MIG_MEM": 0, "MV_FORK_PE": 2, "MV_FORK_MEM": 3},
        4, list(codes),
    )
    # precedence table too short
    assert check_move_codes(codes, 3, list(codes))
    # dispatch forgets a kind
    assert check_move_codes(codes, 4, ["MV_MIG_PE", "MV_MIG_MEM"])


def test_contract_move_layout_trips():
    """The table's rows must be the layout's segments expanded in order,
    and the menu must have one column per row."""
    segs = ((0, 2, 3), (4, 3, 1))
    kind = [0] * 6 + [4] * 3
    arg = [0, 0, 0, 1, 1, 1, 0, 1, 2]
    dest = [0, 1, 2, 0, 1, 2, 0, 0, 0]
    assert not check_move_layout(segs, kind, arg, dest, 9)
    # segments in another order than the rows
    assert check_move_layout(segs[::-1], kind, arg, dest, 9)
    # dest-major rows inside a segment
    assert check_move_layout(
        segs, kind, [0, 1, 0, 1, 0, 1, 0, 1, 2],
        [0, 0, 1, 1, 2, 2, 0, 0, 0], 9,
    )
    # a row the layout does not cover, and a menu one column short
    assert check_move_layout(segs, kind + [4], arg + [3], dest + [0], 10)
    assert check_move_layout(segs, kind, arg, dest, 8)


def test_contract_policy_registry_trips():
    menus = ("naive_sa", "telemetry", "farsi")
    pm = {"naive_sa": "naive_sa", "farsi": "farsi"}
    docs = dict(pm)
    assert not check_policy_registry(pm, menus, docs, list(pm))
    # unknown menu on the class
    assert check_policy_registry(
        {**pm, "farsi": "bogus"}, menus, docs, list(pm)
    )
    # doc disagrees with the class
    assert check_policy_registry(
        pm, menus, {**docs, "farsi": "telemetry"}, list(pm)
    )
    # doc table missing a registered policy
    assert check_policy_registry(
        pm, menus, {"naive_sa": "naive_sa"}, ["naive_sa"]
    )


def test_md_table_parser():
    text = (
        "prose\n\n"
        "| name | selection |\n|---|---|\n| `a` | x |\n| `b` / `c` | y |\n"
        "\nmore prose\n"
    )
    tables = parse_md_tables(text)
    assert len(tables) == 1
    assert tables[0][0] == ["name", "selection"]
    assert len(tables[0]) == 3


# ---------------------------------------------------------------------------
# contracts bound to the real tree
# ---------------------------------------------------------------------------
def test_contracts_hold_on_shipped_tree():
    from repro.analysis.contracts import run_contracts

    findings = run_contracts()
    assert not findings, "\n".join(f.render() for f in findings)


def test_real_taboo_desync_is_caught():
    """PR-9 regression fixture against the REAL fresh_carry: a carry whose
    taboo column is narrower than the real move table must be flagged by
    the same pure check the contract runs."""
    import numpy as np

    from repro.analysis.contracts import _carry_fixture
    from repro.core.device_explore import ChainCarry, MoveTable

    runner, d, ed, cap_pe, cap_mem = _carry_fixture()
    table = MoveTable.of(
        ed, runner.enc, alloc=True, cap_pe=cap_pe, cap_mem=cap_mem
    )
    carry = runner.fresh_carry(
        d, ed, r=2, seed=0, cap_pe=cap_pe, cap_mem=cap_mem, alloc=True
    )
    assert int(carry.taboo.shape[1]) == table.n_moves  # shipped tree holds
    narrowed = carry._replace(taboo=np.asarray(carry.taboo)[:, :-1])
    msgs = check_chain_carry(
        ChainCarry._fields, int(narrowed.taboo.shape[1]), table.n_moves,
        {}, cap_pe, {}, cap_mem,
    )
    assert msgs and any("PR-9" in m for m in msgs)


def test_real_dispatch_and_state_extractors_bind():
    with open(DEVEXP, encoding="utf-8") as fh:
        src = fh.read()
    assert len(dispatch_mv_names(src)) == 10
    state = state_tuple_fields(src)
    assert state is not None and len(state) == 20


def test_kernel_rollup_width_binds():
    with open(os.path.join(
        REPO, "src", "repro", "kernels", "phase_sim", "kernel.py"
    ), encoding="utf-8") as fh:
        assert kernel_rollup_width(fh.read()) == 14


# ---------------------------------------------------------------------------
# acceptance mutation 3: float() injected into the fused chain scan
# ---------------------------------------------------------------------------
def test_fused_block_source_lints_clean():
    with open(DEVEXP, encoding="utf-8") as fh:
        src = fh.read()
    assert not _live(lint_source(src, path="src/repro/core/device_explore.py"))


def test_injected_host_sync_in_chain_scan_is_caught():
    """Textually seed a `float(...)` host sync into the fused block's
    accept step (the `t_it = ...` temperature line inside the scanned
    step) and assert the lint flags it — the scan body is three lexical
    levels below the jit, so this pins the whole scope-propagation
    chain."""
    with open(DEVEXP, encoding="utf-8") as fh:
        src = fh.read()
    needle = "def block(carry, it0, row0, kind, arg, dest):"
    assert needle in src
    mutated = src.replace(
        needle,
        needle + "\n            _leak = float(it0)", 1,
    )
    hits = _live(
        lint_source(mutated, path="src/repro/core/device_explore.py"),
        "host-sync",
    )
    assert hits and any("float" in f.message for f in hits)


# ---------------------------------------------------------------------------
# jaxpr audit
# ---------------------------------------------------------------------------
def test_jaxpr_audit_flags_callback():
    import jax
    import jax.numpy as jnp

    from repro.analysis.jaxpr_audit import audit_jaxpr

    def leaky(x):
        return jax.pure_callback(
            lambda v: v, jax.ShapeDtypeStruct(x.shape, x.dtype), x
        )

    jx = jax.make_jaxpr(leaky)(jnp.zeros((3,), jnp.float32))
    assert audit_jaxpr("leaky", jx, "x.py")


def test_jaxpr_audit_require_missing():
    import jax
    import jax.numpy as jnp

    from repro.analysis.jaxpr_audit import audit_jaxpr

    jx = jax.make_jaxpr(lambda x: x + 1)(jnp.zeros((3,), jnp.float32))
    assert audit_jaxpr("plain", jx, "x.py", require=("pallas_call",))


def test_jaxpr_audit_recurses_into_scan():
    import jax
    import jax.numpy as jnp

    from repro.analysis.jaxpr_audit import collect_primitives

    def f(xs):
        return jax.lax.scan(lambda c, x: (c + jnp.sin(x), c), 0.0, xs)

    prims = collect_primitives(jax.make_jaxpr(f)(jnp.zeros(4)))
    assert "sin" in prims  # lives inside the scan body's sub-jaxpr


def test_jaxpr_audit_clean_on_shipped_entry_points():
    from repro.analysis.jaxpr_audit import run_jaxpr_audit

    findings = run_jaxpr_audit()
    assert not findings, "\n".join(f.render() for f in findings)


def test_bucket_grid_within_bound():
    from repro.analysis.jaxpr_audit import run_jaxpr_audit

    assert not run_jaxpr_audit(entries=["buckets"])


# ---------------------------------------------------------------------------
# CLI gate (the tier-1 wire-in)
# ---------------------------------------------------------------------------
def test_cli_strict_exits_zero_on_shipped_tree():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--strict"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "0 live finding(s)" in out.stderr, out.stderr


def test_finding_key_survives_line_drift():
    f1 = Finding("lint", "host-sync", "m", "p.py", 10, source="x = float(y)")
    f2 = Finding("lint", "host-sync", "m", "p.py", 99, source="x = float(y)")
    assert f1.key() == f2.key()
