"""Tracelint: static analysis of the serving programs' jaxprs and HLO.

The paper's premise is that the computation's *structure* — the
transitive DAG, its execution order — is analyzable ahead of time; this
package is the software twin of that idea. It walks the serving programs
(prefill / decode / paged decode / the DevicePlan forest, per registered
backend) structurally, recursing into ``scan``/``while``/``cond``/
``pjit``/``pallas_call`` sub-jaxprs, and enforces the invariants the
perf story rests on: no host callbacks, gather-only level loops, static
shapes, real KV-cache donation, f32-pure quantize subgraphs, no silent
replication under a mesh. See docs/ANALYSIS.md for the rule catalog.

Three entry points:

* :func:`assert_clean` — the pytest helper replacing the old
  ``"pure_callback" not in str(jaxpr)`` string asserts: trace, lint,
  raise with the offending primitive and its equation path.
* :func:`find_violations` — same, returning the findings (for tests that
  assert a violation *is* present).
* ``python -m repro.analysis.lint`` — the CI gate: every registered
  backend's programs, all rules, allowlist baseline, JSON report.
"""
from __future__ import annotations

import jax
from jax.extend import core

from repro.analysis.baseline import (load_baseline, save_baseline,
                                     split_baselined, stale_keys)
from repro.analysis.costcheck import (CostMetrics, check_budgets,
                                      crosscheck_costmodel, jaxpr_cost,
                                      load_budgets, plan_cost,
                                      program_metrics)
from repro.analysis.planlint import (PlanVerificationError, gate_params,
                                     gate_plan, lint_plans,
                                     list_plan_rules, register_plan_rule,
                                     unregister_plan_rule,
                                     verify_bundle_file,
                                     verify_device_plan, verify_manifest,
                                     verify_plan)
from repro.analysis.programs import (PROGRAM_RULES, build_programs,
                                     lint_backend)
from repro.analysis.rules import (Finding, LintProgram, Rule, get_rule,
                                  list_rules, register_rule, run_rules,
                                  unregister_rule)
from repro.analysis.walker import (CALLBACK_PRIMS, LOOP_PRIMS,
                                   SCATTER_PRIMS, EqnSite, iter_eqns)

__all__ = ["Finding", "LintProgram", "Rule", "EqnSite", "iter_eqns",
           "register_rule", "unregister_rule", "get_rule", "list_rules",
           "run_rules", "build_programs", "lint_backend", "PROGRAM_RULES",
           "load_baseline", "save_baseline", "split_baselined",
           "stale_keys",
           "find_violations", "assert_clean", "DEFAULT_RULES",
           "CALLBACK_PRIMS",
           "SCATTER_PRIMS", "LOOP_PRIMS",
           # plan-IR verifier (planlint.py)
           "PlanVerificationError", "verify_plan", "verify_device_plan",
           "verify_manifest", "verify_bundle_file", "gate_plan",
           "gate_params", "register_plan_rule", "unregister_plan_rule",
           "list_plan_rules", "lint_plans",
           # static cost certifier (costcheck.py)
           "CostMetrics", "jaxpr_cost", "plan_cost", "program_metrics",
           "crosscheck_costmodel", "load_budgets", "check_budgets"]

# the structural rules assert_clean runs when the caller names none: the
# invariant the retired string asserts guarded plus its schedule sibling
# (both jaxpr-level and true of every serving program; gather-only-levels
# is NOT here — model programs legally scatter KV-cache writes inside the
# block scan, so it only guards forest programs and must be requested:
# rules=(*DEFAULT_RULES, "gather-only-levels"))
DEFAULT_RULES = ("no-host-callback", "static-shapes")


def find_violations(fn, *args, rules: tuple[str, ...] = DEFAULT_RULES,
                    name: str = "program", backend: str | None = None,
                    quantize_scopes: tuple[str, ...] = ("quantize_kv",),
                    **program_kw) -> list[Finding]:
    """Trace ``fn(*args)`` (or take a ready ``ClosedJaxpr``) and run the
    named jaxpr-level rules; returns the findings.

    ``program_kw`` forwards extra :class:`LintProgram` evidence
    (``lowered_text=``, ``donate_expect=``, ``mesh=``, ``arrays=``) for
    rules that need more than the jaxpr.
    """
    if isinstance(fn, core.ClosedJaxpr):
        if args:
            raise TypeError("passing args with an already-traced "
                            "ClosedJaxpr makes no sense")
        jaxpr = fn
    else:
        jaxpr = jax.make_jaxpr(fn)(*args)
    prog = LintProgram(name=name, backend=backend, rules=tuple(rules),
                      jaxpr=jaxpr, quantize_scopes=quantize_scopes,
                      **program_kw)
    return run_rules(prog)


def assert_clean(fn, *args, rules: tuple[str, ...] = DEFAULT_RULES,
                 baseline: frozenset[str] | tuple[str, ...] = (),
                 **kw) -> None:
    """Assert ``fn(*args)``'s program violates none of ``rules``.

    The drop-in replacement for the old string asserts: on violation the
    AssertionError names every offending primitive and its equation path
    inside the (possibly nested) jaxpr — not just "the string appeared".
    """
    findings = find_violations(fn, *args, rules=rules, **kw)
    new, _ = split_baselined(findings, frozenset(baseline))
    if new:
        lines = "\n  ".join(f.format() for f in new)
        raise AssertionError(
            f"tracelint: {len(new)} violation(s) of "
            f"{', '.join(rules)}:\n  {lines}")
