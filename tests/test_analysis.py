"""Tracelint (ISSUE 7): the static-analysis pass and its rule registry.

Three tiers in one module:

* registry + walker mechanics: ``register_rule`` duplicate/replace
  semantics, unknown-name errors that list the registry, recursive
  equation iteration through ``scan``/``cond``/``pjit`` sub-jaxprs with
  loop membership and inherited ``jax.named_scope`` scopes.
* a positive control per rule — a deliberately violating program each
  rule MUST flag (the analyzer's own acceptance criterion: a lint gate
  that cannot fire is weaker than no gate).
* the public surface: ``assert_clean`` raises with primitive + equation
  path, baselines suppress, ``lint_backend`` honors ``lint_exempt``
  capability tags, and a real backend's program set lints clean
  end-to-end.
"""
import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
import pytest

from repro import analysis
from repro.analysis import rules as R
from repro.analysis.baseline import (load_baseline, save_baseline,
                                     split_baselined)
from repro.analysis.rules import aliased_args
from repro.analysis.walker import iter_eqns


# -- registry ----------------------------------------------------------------

class _DummyRule(R.Rule):
    name = "dummy-test-rule"
    description = "registry test fixture"

    def check(self, prog):
        return []


def test_registry_duplicate_is_loud_and_replace_works():
    r1, r2 = _DummyRule(), _DummyRule()
    R.register_rule(r1)
    try:
        with pytest.raises(ValueError, match="already registered"):
            R.register_rule(r2)
        assert R.register_rule(r2, replace=True) is r2
        assert R.get_rule("dummy-test-rule") is r2
    finally:
        R.unregister_rule("dummy-test-rule")
    assert "dummy-test-rule" not in R.list_rules()


def test_registry_unknown_names_list_registry():
    with pytest.raises(KeyError, match="no-host-callback"):
        R.get_rule("no-such-rule")
    with pytest.raises(KeyError, match="registered rules"):
        R.unregister_rule("no-such-rule")


def test_rule_must_declare_name():
    class Nameless(R.Rule):
        def check(self, prog):
            return []
    with pytest.raises(ValueError, match="name"):
        R.register_rule(Nameless())


def test_builtin_rules_all_registered():
    names = R.list_rules()
    for expect in ("no-host-callback", "gather-only-levels",
                   "static-shapes", "kv-donation", "dtype-purity",
                   "sharding-integrity"):
        assert expect in names, names


# -- walker ------------------------------------------------------------------

def test_walker_recurses_with_loop_membership_and_paths():
    def f(x):
        def body(c, _):
            y = lax.cond(c.sum() > 0, lambda v: v * 2, lambda v: v + 1, c)
            return y, None
        out, _ = lax.scan(body, x, None, length=3)
        return out + 1

    sites = list(iter_eqns(jax.make_jaxpr(f)(jnp.ones((4,)))))
    prims = {s.primitive for s in sites}
    assert "scan" in prims and "cond" in prims
    # everything under the scan body is loop-resident; the trailing add
    # at top level is not
    in_scan = [s for s in sites if "scan/" in s.path]
    assert in_scan and all(s.in_loop for s in in_scan)
    top = [s for s in sites if "/" not in s.path]
    assert top and not any(s.in_loop for s in top)
    # paths are eqn-indexed and nest ("3:scan/jaxpr/0:cond/branches/...")
    assert any(s.path.count("/") >= 2 for s in sites)


def test_walker_inherits_named_scopes_into_subjaxprs():
    def f(x):
        with jax.named_scope("quantize_kv"):
            def body(c, _):
                return c * 2.0, None
            y, _ = lax.scan(body, x, None, length=2)
        return y + 1.0

    sites = list(iter_eqns(jax.make_jaxpr(f)(jnp.ones((4,)))))
    inner = [s for s in sites if "scan/" in s.path]
    assert inner and all("quantize_kv" in s.scopes for s in inner)
    top_add = [s for s in sites if s.primitive == "add"]
    assert top_add and not any("quantize_kv" in s.scopes
                               for s in top_add)


# -- positive controls: each rule fires on a violating program ---------------

def test_control_no_host_callback_fires():
    def f(x):
        return jax.pure_callback(
            np.sin, jax.ShapeDtypeStruct((4,), jnp.float32), x)
    found = analysis.find_violations(f, jnp.ones((4,), jnp.float32),
                                     rules=("no-host-callback",))
    assert found and found[0].primitive == "pure_callback"
    assert "pure_callback" in found[0].path


def test_control_gather_only_levels_fires_inside_scan_only():
    def scatter_in_loop(x):
        def body(c, _):
            return c.at[0].set(c.sum()), None
        y, _ = lax.scan(body, x, None, length=3)
        return y

    found = analysis.find_violations(scatter_in_loop, jnp.ones((4,)),
                                     rules=("gather-only-levels",))
    assert found and found[0].rule == "gather-only-levels"
    assert found[0].primitive.startswith("scatter")
    assert "scan/" in found[0].path

    # the same scatter OUTSIDE any loop is the legal direct dispatch
    assert analysis.find_violations(
        lambda x: x.at[0].set(x.sum()), jnp.ones((4,)),
        rules=("gather-only-levels",)) == []


def test_control_static_shapes_fires_on_while():
    def f(x):
        return lax.while_loop(lambda c: c[0] < 10,
                              lambda c: (c[0] + 1, c[1] * 2.0),
                              (jnp.int32(0), x))
    found = analysis.find_violations(f, jnp.ones((4,)),
                                     rules=("static-shapes",))
    assert found and found[0].primitive == "while"
    # fori_loop with static bounds lowers to scan: clean
    assert analysis.find_violations(
        lambda x: lax.fori_loop(0, 4, lambda i, c: c * 2.0, x),
        jnp.ones((4,)), rules=("static-shapes",)) == []


def test_control_kv_donation_fires_when_lowering_drops_donation():
    def f(p, cache):
        return cache + p

    x = jnp.zeros((64,), jnp.float32)
    undonated = jax.jit(f, keep_unused=True).lower(x, x).as_text()
    prog = R.LintProgram(name="decode", rules=("kv-donation",),
                         lowered_text=undonated,
                         donate_expect={"kv-cache": (1, 2)})
    found = R.run_rules(prog)
    assert found and found[0].rule == "kv-donation"
    assert "NOT aliased" in found[0].message

    donated = jax.jit(f, donate_argnums=(1,),
                      keep_unused=True).lower(x, x).as_text()
    prog.lowered_text = donated
    assert R.run_rules(prog) == []


def test_aliased_args_reads_both_donation_markers():
    # single-device lowering: input aliased to a concrete output
    single = ('func.func public @main(%arg0: tensor<4xf32>, '
              '%arg1: tensor<4xf32> {tf.aliasing_output = 0 : i32}) {')
    assert aliased_args(single) == {1}
    # mesh lowering: pairing deferred to the compiler
    meshed = ('func.func public @main(%arg0: tensor<4xf32> '
              '{jax.buffer_donor = true, mhlo.sharding = "..."}, '
              '%arg1: tensor<4xf32>) {')
    assert aliased_args(meshed) == {0}
    assert aliased_args("func.func @main(%arg0: tensor<4xf32>) {") == set()


def test_control_dtype_purity_fires_on_bf16_in_quantize_scope():
    def bad(x):
        with jax.named_scope("quantize_kv"):
            scale = (jnp.max(jnp.abs(x), -1, keepdims=True)
                     .astype(jnp.bfloat16) / 127.0)
        return x / scale.astype(jnp.float32)

    found = analysis.find_violations(bad, jnp.ones((4, 8), jnp.float32),
                                     rules=("dtype-purity",))
    assert found and "quantize_kv" in found[0].message

    # the clean shape: cast INTO f32 first (attention._quantize_kv) —
    # the convert's *output* is f32, so bf16 inputs do not trip the rule
    def good(x):
        with jax.named_scope("quantize_kv"):
            x32 = x.astype(jnp.float32)
            return x32 / (jnp.max(jnp.abs(x32), -1, keepdims=True) / 127.)
    assert analysis.find_violations(
        good, jnp.ones((4, 8), jnp.bfloat16),
        rules=("dtype-purity",)) == []

    # bf16 arithmetic OUTSIDE a quantize scope is fine (model math)
    assert analysis.find_violations(
        lambda x: x * 2, jnp.ones((4,), jnp.bfloat16),
        rules=("dtype-purity",)) == []


def test_control_dtype_purity_fires_on_f64_anywhere():
    with jax.enable_x64(True):
        jaxpr = jax.make_jaxpr(lambda x: x * 2.0)(
            jnp.ones((4,), jnp.float64))
    found = analysis.find_violations(jaxpr, rules=("dtype-purity",))
    assert found and "float64" in found[0].message


class _MockSharding:
    def __init__(self, replicated):
        self.is_fully_replicated = replicated


class _MockLeaf:
    def __init__(self, shape, replicated, itemsize=4):
        self.shape = shape
        self.nbytes = int(np.prod(shape)) * itemsize
        self.sharding = _MockSharding(replicated)


class _MockMesh:
    def __init__(self, **shape):
        self.shape = shape
        self.axis_names = tuple(shape)


def test_control_sharding_integrity_fires_on_replicated_cache():
    prog = R.LintProgram(
        name="decode", rules=("sharding-integrity",),
        mesh=_MockMesh(data=4),
        arrays={"kv-cache": {"k": _MockLeaf((4, 16, 64), replicated=True),
                             "v": _MockLeaf((4, 16, 64),
                                            replicated=False)}})
    found = R.run_rules(prog)
    assert len(found) == 1 and found[0].rule == "sharding-integrity"
    assert "kv-cache" in found[0].path and "'k'" in found[0].path
    assert "fully replicated" in found[0].message

    # scalars/small arrays (step counters) are exempt by min_bytes
    prog.arrays = {"kv-cache": {"step": _MockLeaf((4,), replicated=True)}}
    assert R.run_rules(prog) == []

    # a 1-device mesh has nothing to shard over
    prog.arrays = {"kv-cache": {"k": _MockLeaf((4, 16, 64), True)}}
    prog.mesh = _MockMesh(data=1)
    assert R.run_rules(prog) == []


# -- public surface ----------------------------------------------------------

def test_assert_clean_passes_and_raises_with_location():
    analysis.assert_clean(lambda x: x * 2, jnp.ones((4,)))
    def dirty(x):
        return jax.pure_callback(
            np.sin, jax.ShapeDtypeStruct((4,), jnp.float32), x)
    with pytest.raises(AssertionError, match="no-host-callback") as ei:
        analysis.assert_clean(dirty, jnp.ones((4,), jnp.float32))
    assert "pure_callback" in str(ei.value)   # primitive + path, not
    assert ":" in str(ei.value)               # just "string appeared"


def test_assert_clean_baseline_suppresses():
    def dirty(x):
        return jax.pure_callback(
            np.sin, jax.ShapeDtypeStruct((4,), jnp.float32), x)
    found = analysis.find_violations(dirty, jnp.ones((4,), jnp.float32))
    analysis.assert_clean(dirty, jnp.ones((4,), jnp.float32),
                          baseline=tuple(f.key() for f in found))


def test_find_violations_rejects_args_with_ready_jaxpr():
    jaxpr = jax.make_jaxpr(lambda x: x + 1)(jnp.ones((4,)))
    with pytest.raises(TypeError, match="ClosedJaxpr"):
        analysis.find_violations(jaxpr, jnp.ones((4,)))


def test_baseline_roundtrip(tmp_path):
    def dirty(x):
        return jax.pure_callback(
            np.sin, jax.ShapeDtypeStruct((4,), jnp.float32), x)
    found = analysis.find_violations(dirty, jnp.ones((4,), jnp.float32))
    p = tmp_path / "lint_baseline.txt"
    n = save_baseline(str(p), found)
    assert n == len({f.key() for f in found})
    loaded = load_baseline(str(p))
    new, suppressed = split_baselined(found, loaded)
    assert new == [] and suppressed == found
    # comments and blanks are ignored; unknown path is loud
    p.write_text("# comment\n\n" + found[0].key() + "\n")
    assert load_baseline(str(p)) == {found[0].key()}
    with pytest.raises(FileNotFoundError):
        load_baseline(str(tmp_path / "missing.txt"))
    assert load_baseline(None) == frozenset()


def test_run_rules_honors_exemption_and_skips_missing_evidence():
    jaxpr = jax.make_jaxpr(lambda x: jax.pure_callback(
        np.sin, jax.ShapeDtypeStruct((4,), jnp.float32), x))(
            jnp.ones((4,), jnp.float32))
    prog = R.LintProgram(
        name="decode", rules=("no-host-callback", "kv-donation"),
        jaxpr=jaxpr)                       # no lowered_text
    # kv-donation silently skipped (no evidence); callback found
    assert [f.rule for f in R.run_rules(prog)] == ["no-host-callback"]
    # the host-oracle backend's exemption silences its one legal callback
    assert R.run_rules(prog,
                       exempt=frozenset({"no-host-callback"})) == []


def test_engine_backend_declares_callback_exemption():
    from repro.core.backend import get_backend
    assert "no-host-callback" in get_backend("engine").lint_exempt
    assert get_backend("engine_jit").lint_exempt == frozenset()
    profile = get_backend("engine").lint_profile()
    assert profile["no-host-callback"] is False
    assert profile["kv-donation"] is True


def test_lint_backend_end_to_end_clean():
    """The acceptance smoke: a real registered backend's whole program
    set (prefill, donated decode, paged decode + its post-hot-swap twin,
    the two fast-path programs, forest) lints clean."""
    from repro.analysis.programs import lint_backend
    progs, findings = lint_backend("engine_jit", n_layers=1, batch=2)
    assert [p.name for p in progs] == ["prefill", "decode",
                                      "paged-decode",
                                      "paged-decode-swapped",
                                      "paged-attention",
                                      "prefill-bucketed", "forest"]
    assert findings == [], [f.format() for f in findings]


def test_lint_cli_single_backend(capsys):
    """`python -m repro.analysis.lint --backend int_dot` exits 0 and
    reports per-backend status lines."""
    from repro.analysis.lint import main
    rc = main(["--backend", "int_dot", "--batch", "2"])
    out = capsys.readouterr().out
    assert rc == 0 and "int_dot" in out and "clean" in out


def test_lint_cli_list_rules(capsys):
    from repro.analysis.lint import main
    assert main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for name in R.list_rules():
        assert name in out


# -- ISSUE 10 satellites: custom-derivative recursion + baseline pruning -----

def test_control_callback_found_under_custom_jvp():
    """A pure_callback cannot hide behind jax.custom_jvp: the walker
    enters the primal call_jaxpr of custom_jvp_call."""
    @jax.custom_jvp
    def f(x):
        return jax.pure_callback(
            np.sin, jax.ShapeDtypeStruct((4,), jnp.float32), x)

    @f.defjvp
    def f_jvp(primals, tangents):
        (x,), (dx,) = primals, tangents
        return f(x), jnp.cos(x) * dx

    found = analysis.find_violations(
        lambda x: f(x) * 2.0, jnp.ones((4,), jnp.float32),
        rules=("no-host-callback",))
    assert found and found[0].primitive == "pure_callback"
    assert "custom_jvp_call" in found[0].path


def test_control_callback_found_under_custom_vjp():
    @jax.custom_vjp
    def f(x):
        return jax.pure_callback(
            np.sin, jax.ShapeDtypeStruct((4,), jnp.float32), x)

    f.defvjp(lambda x: (f(x), x), lambda res, g: (g * jnp.cos(res),))

    found = analysis.find_violations(
        lambda x: f(x) + 1.0, jnp.ones((4,), jnp.float32),
        rules=("no-host-callback",))
    assert found and found[0].primitive == "pure_callback"
    assert "custom_vjp_call" in found[0].path


def test_baseline_stale_keys_and_prune():
    from repro.analysis.baseline import stale_keys

    def dirty(x):
        return jax.pure_callback(
            np.sin, jax.ShapeDtypeStruct((4,), jnp.float32), x)
    found = analysis.find_violations(dirty, jnp.ones((4,), jnp.float32))
    live = found[0].key()
    dead = "no-host-callback::engine::retired-program::pure_callback"
    assert stale_keys({live, dead}, found) == [dead]
    assert stale_keys({live}, found) == []
    assert stale_keys(set(), found) == []


def test_lint_cli_prune_baseline(tmp_path, capsys):
    """`lint --prune-baseline` reports stale allowlist entries and, with
    --write-baseline, rewrites the file without them."""
    from repro.analysis.lint import main
    dead = "no-host-callback::int_dot::retired-program::pure_callback"
    p = tmp_path / "baseline.txt"
    p.write_text(dead + "\n")
    rc = main(["--backend", "int_dot", "--batch", "2",
               "--baseline", str(p), "--prune-baseline"])
    out = capsys.readouterr().out
    assert rc == 0
    assert f"stale: {dead}" in out and "1 stale entry" in out
    rc = main(["--backend", "int_dot", "--batch", "2",
               "--baseline", str(p), "--prune-baseline",
               "--write-baseline", str(p)])
    assert rc == 0
    assert dead not in p.read_text()


def test_lint_cli_plans_and_budgets_sections(tmp_path, capsys):
    """--plans/--budgets merge into the findings stream and the JSON
    report gains their sections."""
    import json as _json

    from repro.analysis.lint import main
    from repro.core.plancache import PlanCache, set_default_cache
    prev = set_default_cache(PlanCache(capacity=64))
    try:
        out_json = tmp_path / "lint.json"
        rc = main(["--backend", "engine_jit", "--plans", "--budgets",
                   "--json", str(out_json)])
    finally:
        set_default_cache(prev)
    out = capsys.readouterr().out
    assert rc == 0
    assert "[planlint]" in out and "[costcheck]" in out
    doc = _json.loads(out_json.read_text())
    assert doc["plans"] and doc["plans"][0]["backend"] == "engine_jit"
    assert any(r.get("ok") for r in doc["budgets"])
