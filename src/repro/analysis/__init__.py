"""repro.analysis — static contract checker, JAX lint, and jaxpr audit.

Three passes that make the repo's recurring desync bugs un-shippable
(``python -m repro.analysis --strict`` gates tier-1 and the bench smoke):

* :mod:`~repro.analysis.contracts` — cross-file layout contracts
  (ChainCarry/MoveTable widths, MV_* dispatch
  coverage, policy registry vs docs).
* :mod:`~repro.analysis.lint` — AST rules over ``src/repro/`` for
  host-sync and retracing hazards inside traced scopes.
* :mod:`~repro.analysis.jaxpr_audit` — traces the hot jitted entry
  points and asserts forbidden/required primitives and the jit-cache
  key bound.

See ``docs/ANALYSIS.md`` for the rule list, the ``# repro: noqa[rule]:
reason`` suppression format, and the baseline workflow.

This package is import-light on purpose: importing it (or running
``--help``) must not pull in jax — the passes import their subjects
lazily when they run.
"""
from .findings import Finding, format_findings

__all__ = [
    "Finding",
    "format_findings",
    "run_all",
    "run_contracts",
    "run_lint",
    "run_jaxpr_audit",
]


def run_contracts(*args, **kwargs):
    from .contracts import run_contracts as _rc

    return _rc(*args, **kwargs)


def run_lint(*args, **kwargs):
    from .lint import run_lint as _rl

    return _rl(*args, **kwargs)


def run_jaxpr_audit(*args, **kwargs):
    from .jaxpr_audit import run_jaxpr_audit as _rj

    return _rj(*args, **kwargs)


def run_all(passes=("contracts", "lint", "jaxpr")):
    """All findings from the selected passes, baseline/noqa applied to
    lint (the other passes have no baseline — a contract either holds or
    the build is wrong)."""
    findings = []
    if "contracts" in passes:
        findings.extend(run_contracts())
    if "lint" in passes:
        from .lint import apply_baseline, load_baseline

        findings.extend(apply_baseline(run_lint(), load_baseline()))
    if "jaxpr" in passes:
        findings.extend(run_jaxpr_audit())
    return findings
