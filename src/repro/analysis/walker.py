"""Structural jaxpr walker: every equation, at every nesting depth.

The string asserts this package replaces (``"pure_callback" not in
str(jaxpr)``) matched the *printed* jaxpr — they could false-positive on a
variable name, could not say which equation violated, and silently
depended on the printer recursing. This walker recurses for real: any
``ClosedJaxpr`` / ``Jaxpr`` found in an equation's params (``scan`` and
``while`` bodies, ``cond`` branches, ``pjit``/``remat``/``custom_*`` call
jaxprs, ``pallas_call`` kernel jaxprs, ...) is entered, and every visited
equation comes back as an :class:`EqnSite` carrying

* ``path`` — the equation's address, e.g.
  ``"12:scan/jaxpr/3:pjit/jaxpr/0:scatter"`` (index ``:`` primitive at
  each level), printable in a finding;
* ``in_loop`` — whether any enclosing equation is a ``scan``/``while``
  body (the level-loop invariants key on this);
* ``scopes`` — the union of ``jax.named_scope`` components on the
  equation itself and on every enclosing call equation (sub-jaxpr
  equations carry only their local name stack, so scope membership must
  be inherited down the walk).

Primitive-name sets used by several rules live here so rules and tests
share one spelling.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Iterator

from jax.extend import core

__all__ = ["EqnSite", "iter_eqns", "subjaxprs", "CALLBACK_PRIMS",
           "SCATTER_PRIMS", "LOOP_PRIMS", "CALL_PRIMS"]

# host-callback family: anything that escapes the device program
CALLBACK_PRIMS = frozenset({"pure_callback", "io_callback",
                            "debug_callback"})
# scatter family (jax spells variants with a hyphen)
SCATTER_PRIMS = frozenset({"scatter", "scatter-add", "scatter-sub",
                           "scatter-mul", "scatter-min", "scatter-max",
                           "scatter-apply"})
# primitives whose sub-jaxprs execute repeatedly (loop bodies)
LOOP_PRIMS = frozenset({"scan", "while"})
# call-like primitives (enter exactly once; not loops)
CALL_PRIMS = frozenset({"pjit", "cond", "remat2", "custom_jvp_call",
                        "custom_vjp_call", "custom_vjp_call_jaxpr",
                        "pallas_call", "closed_call", "core_call",
                        "xla_call"})


@dataclasses.dataclass(frozen=True)
class EqnSite:
    """One visited equation with its structural context."""
    eqn: Any                      # jax.extend.core.JaxprEqn
    path: str                     # "12:scan/jaxpr/0:scatter"
    in_loop: bool                 # inside any scan/while body
    scopes: frozenset[str]        # inherited named_scope components

    @property
    def primitive(self) -> str:
        return self.eqn.primitive.name


def subjaxprs(eqn) -> Iterator[tuple[str, core.Jaxpr]]:
    """(param-key, jaxpr) for every sub-jaxpr in ``eqn.params``.

    ``while`` keeps its two jaxprs under ``cond_jaxpr``/``body_jaxpr``;
    ``cond`` keeps a tuple under ``branches``; most call-likes keep one
    under ``jaxpr``/``call_jaxpr``. The custom-derivative wrappers are
    covered the same way — ``custom_jvp_call`` carries its primal under
    ``call_jaxpr`` and ``custom_vjp_call``/``custom_vjp_call_jaxpr``
    under ``fun_jaxpr``, so a callback or scatter cannot hide behind a
    ``jax.custom_jvp``/``jax.custom_vjp`` decorator (positive controls in
    tests/test_analysis.py). Rather than enumerate primitives, look at
    the values: anything that *is* a jaxpr gets walked.
    """
    for key, val in eqn.params.items():
        vals = val if isinstance(val, (tuple, list)) else (val,)
        for i, v in enumerate(vals):
            label = key if len(vals) == 1 else f"{key}[{i}]"
            if isinstance(v, core.ClosedJaxpr):
                yield label, v.jaxpr
            elif isinstance(v, core.Jaxpr):
                yield label, v


def _eqn_scopes(eqn) -> frozenset[str]:
    stack = getattr(eqn.source_info, "name_stack", None)
    s = str(stack) if stack is not None else ""
    return frozenset(p for p in s.split("/") if p)


def iter_eqns(jaxpr, *, _path: str = "", _in_loop: bool = False,
              _scopes: frozenset[str] = frozenset()) -> Iterator[EqnSite]:
    """Yield an :class:`EqnSite` for every equation, recursing into every
    sub-jaxpr. Accepts a ``ClosedJaxpr`` or a ``Jaxpr``."""
    if isinstance(jaxpr, core.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    for i, eqn in enumerate(jaxpr.eqns):
        name = eqn.primitive.name
        here = f"{_path}{i}:{name}"
        scopes = _scopes | _eqn_scopes(eqn)
        yield EqnSite(eqn=eqn, path=here, in_loop=_in_loop, scopes=scopes)
        loop = _in_loop or name in LOOP_PRIMS
        for label, sub in subjaxprs(eqn):
            # a while's cond jaxpr runs per iteration too — both count
            yield from iter_eqns(sub, _path=f"{here}/{label}/",
                                 _in_loop=loop, _scopes=scopes)
