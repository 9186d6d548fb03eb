"""The multi-device serve cell (ISSUE 5) + greedy-decode contract fixes.

Two tiers in one module:

* contract tests (any device count): the explicit ``greedy_generate``
  ``n_steps`` semantics (``n_steps=0`` returns no tokens; the old loop
  always emitted the prefill argmax), decode-step cache donation, jit
  memoisation across ``greedy_generate`` calls, the
  ``ShardingDropWarning`` on silently-replicated spec axes (including the
  multi-axis ``("pod", "data")`` product rule), and the capability-keyed
  ``plan_specs`` mesh-attach hook.
* mesh tests (skipped below 4 local devices): ``greedy_generate`` on a
  4-way ``P("data")`` mesh with attached DevicePlans is bit-identical to
  the 1-device run for ``engine_jit`` and ``engine_pallas``, decode makes
  zero PlanCache lookups, the lowered decode jaxpr stays
  ``pure_callback``-free under the mesh, and the KV caches are genuinely
  data-sharded (not silently replicated). CI runs these in a dedicated
  ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` leg; locally:

      XLA_FLAGS=--xla_force_host_platform_device_count=4 \
          PYTHONPATH=src python -m pytest -q tests/test_serve_mesh.py

  A slow-marked subprocess twin keeps the acceptance property reachable
  from a 1-device host via ``-m slow`` (test_distributed.py's pattern).
"""
import os
import subprocess
import sys
import textwrap
import warnings

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import analysis
from repro.configs import get_reduced
from repro.distributed import sharding as SH
from repro.launch.mesh import make_serve_mesh, parse_mesh_spec
from repro.launch.specs import serve_config
from repro.models.model import Model
from repro.train.serve_step import (_jit_decode_step, _jit_prefill,
                                    greedy_generate, make_decode_step)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NDEV = len(jax.devices())
needs_mesh = pytest.mark.skipif(
    NDEV < 4, reason="needs >= 4 devices "
    "(XLA_FLAGS=--xla_force_host_platform_device_count=4)")

MESH_BACKENDS = ("engine_jit", "engine_pallas")


@pytest.fixture
def cache():
    """Fresh process-default plan cache per test; restores the previous."""
    from repro.core.plancache import PlanCache, set_default_cache
    c = PlanCache(capacity=64)
    prev = set_default_cache(c)
    yield c
    set_default_cache(prev)


@pytest.fixture(scope="module")
def fp_model():
    cfg = get_reduced("smollm_135m").replace(n_layers=2, dtype=jnp.float32)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 8),
                                          0, cfg.vocab, jnp.int32)}
    return model, params, batch


def _quant_cell(backend: str):
    cfg = serve_config(get_reduced("smollm_135m").replace(n_layers=2),
                       backend=backend)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 8),
                                          0, cfg.vocab, jnp.int32)}
    return model, params, batch


def _data_mesh(n: int):
    return make_serve_mesh({"data": n})


# -- greedy_generate contract ------------------------------------------------

def test_n_steps_is_token_count(fp_model):
    """n_steps == tokens returned; n_steps=0 is empty, not 1 token (the
    old off-by-one); shorter runs are prefixes of longer ones (greedy)."""
    model, params, batch = fp_model
    t0 = greedy_generate(model, params, batch, max_len=32, n_steps=0)
    assert t0.shape == (2, 0) and t0.dtype == jnp.int32
    t1 = np.asarray(greedy_generate(model, params, batch, max_len=32,
                                    n_steps=1))
    t5 = np.asarray(greedy_generate(model, params, batch, max_len=32,
                                    n_steps=5))
    assert t1.shape == (2, 1) and t5.shape == (2, 5)
    np.testing.assert_array_equal(t1, t5[:, :1])
    assert (t5 >= 0).all() and (t5 < model.cfg.vocab).all()


def test_negative_n_steps_raises(fp_model):
    model, params, batch = fp_model
    with pytest.raises(ValueError, match="n_steps"):
        greedy_generate(model, params, batch, max_len=32, n_steps=-1)


def test_decode_step_donates_caches(fp_model):
    """The decode jit donates the KV caches — without donation every token
    pays a full cache-buffer copy."""
    model, params, batch = fp_model
    logits, caches = _jit_prefill(model, 32)(params, batch)
    tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
    probe = jax.tree_util.tree_leaves(caches["body"])[0]
    _, new_caches = _jit_decode_step(model, True)(params, caches, tok,
                                                  jnp.int32(8))
    assert probe.is_deleted()
    # donate=False keeps the input alive (re-enterable decode)
    probe2 = jax.tree_util.tree_leaves(new_caches["body"])[0]
    _jit_decode_step(model, False)(params, new_caches, tok, jnp.int32(9))
    assert not probe2.is_deleted()


def test_jitted_steps_memoised_across_calls(fp_model):
    """Repeated greedy_generate calls must not rebuild the jit wrappers
    (a rebuilt closure means a retrace per serving call)."""
    model, _, _ = fp_model
    assert _jit_prefill(model, 32) is _jit_prefill(model, 32)
    assert _jit_decode_step(model, True) is _jit_decode_step(model, True)
    assert _jit_decode_step(model, True) is not _jit_decode_step(model,
                                                                 False)


def test_jit_memo_keys_include_mesh(fp_model):
    """The _STEP_JITS memo keys carry the ambient mesh: a step traced
    under ``set_mesh`` bakes the mesh into its sharding constraints, but
    jit's own cache only keys on avals — interleaved mesh / no-mesh
    ``greedy_generate`` calls must get distinct jit objects, and the
    tokens must not drift across the interleaving."""
    model, params, batch = fp_model
    mesh = _data_mesh(1)
    assert _jit_prefill(model, 32) is not _jit_prefill(model, 32, mesh)
    assert _jit_prefill(model, 32, mesh) is _jit_prefill(model, 32, mesh)
    assert _jit_decode_step(model, True) is not \
        _jit_decode_step(model, True, mesh)
    assert _jit_decode_step(model, True, mesh) is \
        _jit_decode_step(model, True, mesh)
    # mesh -> no-mesh -> mesh interleaving: bit-identical throughout
    t_plain = np.asarray(greedy_generate(model, params, batch,
                                         max_len=32, n_steps=4))
    t_mesh = np.asarray(greedy_generate(model, params, batch,
                                        max_len=32, n_steps=4, mesh=mesh))
    t_plain2 = np.asarray(greedy_generate(model, params, batch,
                                          max_len=32, n_steps=4))
    np.testing.assert_array_equal(t_plain, t_mesh)
    np.testing.assert_array_equal(t_plain, t_plain2)


# -- sharding.spec non-divisibility warning ---------------------------------

class _FakeMesh:
    """Duck-typed mesh: spec(mesh=) only needs axis_names + shape."""

    def __init__(self, **shape):
        self.axis_names = tuple(shape)
        self.shape = shape


def test_spec_warns_once_on_dropped_axis():
    SH._WARNED_DROPS.clear()
    mesh = _FakeMesh(pod=2, data=2)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        # 6 % (2*2) != 0 — the multi-axis batch rule drops on the PRODUCT
        s = SH.spec("batch", None, shape=(6, 16), mesh=mesh)
        assert s == jax.sharding.PartitionSpec(None, None)
        # same drop again: deduplicated
        SH.spec("batch", None, shape=(6, 16), mesh=mesh)
    drops = [x for x in w if issubclass(x.category, SH.ShardingDropWarning)]
    assert len(drops) == 1
    msg = str(drops[0].message)
    assert "batch" in msg and "4" in msg and "6" in msg

    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        # divisible: sharded, no warning
        s = SH.spec("batch", None, shape=(8, 16), mesh=mesh)
        assert s == jax.sharding.PartitionSpec(("pod", "data"), None)
        # no shape given: caller opted out of divisibility fitting
        SH.spec("batch", None, mesh=mesh)
        # a different dropped dim is a different event — warns again
        SH.spec("batch", None, shape=(10, 16), mesh=mesh)
    assert sum(issubclass(x.category, SH.ShardingDropWarning)
               for x in w) == 1


def test_single_axis_drop_warns():
    SH._WARNED_DROPS.clear()
    mesh = _FakeMesh(model=16)
    with pytest.warns(SH.ShardingDropWarning, match="kv_heads"):
        assert SH.spec("kv_heads", shape=(8,), mesh=mesh) == \
            jax.sharding.PartitionSpec(None)


# -- capability-keyed mesh attach -------------------------------------------

def test_attach_consults_backend_plan_specs(cache):
    """attach_device_plans(mesh=) with no explicit specs asks the backend's
    plan_specs hook for the placement; explicit specs bypass it."""
    import repro.core.backend as BK
    from repro.core.plancache import attach_device_plans
    from repro.quant import QuantConfig, linear_init

    calls = []

    class Placed(BK.EngineJitBackend):
        name = "custom_placed"

        def plan_specs(self, mesh):
            calls.append(mesh)
            return jax.sharding.PartitionSpec()

    BK.register_backend(Placed())
    try:
        cfg = QuantConfig(mode="ptq", w_bits=4, a_bits=8, group=64,
                          backend="custom_placed")
        layer = linear_init(jax.random.PRNGKey(0), 128, 16, cfg)
        mesh = _data_mesh(1)
        out = attach_device_plans({"l": layer}, cfg, cache=cache, mesh=mesh)
        assert len(calls) == 1 and calls[0] is mesh
        assert "dplan" in out["l"]
        attach_device_plans({"l": layer}, cfg, cache=cache, mesh=mesh,
                            specs=jax.sharding.PartitionSpec())
        assert len(calls) == 1          # explicit specs: hook not consulted
    finally:
        BK.unregister_backend("custom_placed")


def test_parse_mesh_spec():
    assert parse_mesh_spec("data=4") == {"data": 4}
    assert parse_mesh_spec("pod=2,data=2") == {"pod": 2, "data": 2}
    for bad in ("data", "data=", "data=0", "=4", "data=4,data=2"):
        with pytest.raises(ValueError):
            parse_mesh_spec(bad)
    with pytest.raises(ValueError, match="devices"):
        make_serve_mesh({"data": 10 * NDEV})


# -- the mesh serve cell (needs forced host devices) ------------------------

@needs_mesh
@pytest.mark.parametrize("backend", MESH_BACKENDS)
def test_mesh_generate_bit_identical_and_no_cache_traffic(backend, cache):
    """The acceptance property: 4-way P('data') greedy_generate with
    attached DevicePlans returns bit-identical tokens to the 1-device run,
    and decode resolves every plan from the params — zero PlanCache
    lookups (misses OR hits) after attach."""
    model, params, batch = _quant_cell(backend)
    toks1 = np.asarray(greedy_generate(
        model, model.attach_device_plans(params), batch,
        max_len=24, n_steps=5))
    mesh = _data_mesh(4)
    params_m = model.attach_device_plans(params, mesh=mesh)
    cache.reset_stats()
    toks_n = np.asarray(greedy_generate(model, params_m, batch,
                                        max_len=24, n_steps=5, mesh=mesh))
    np.testing.assert_array_equal(toks1, toks_n)
    s = cache.stats()
    assert s["misses"] == 0 and s["hits"] == 0, s


@needs_mesh
def test_mesh_matches_int_dot_reference(cache):
    """The mesh cell stays on the bit-exactness pyramid: engine_jit on the
    mesh == int_dot on one device (same quantized init)."""
    model, params, batch = _quant_cell("engine_jit")
    mesh = _data_mesh(4)
    toks_n = np.asarray(greedy_generate(
        model, model.attach_device_plans(params, mesh=mesh), batch,
        max_len=24, n_steps=5, mesh=mesh))
    ref_model = Model(model.cfg.replace(
        quant=model.cfg.quant.with_(backend="int_dot")))
    toks_ref = np.asarray(greedy_generate(ref_model, params, batch,
                                          max_len=24, n_steps=5))
    np.testing.assert_array_equal(toks_ref, toks_n)


@needs_mesh
def test_mesh_decode_jaxpr_callback_free_and_caches_sharded(cache):
    """Under the mesh the decode jaxpr has zero pure_callbacks, and the
    prefill-built KV caches are actually data-sharded (the silent-
    replication failure mode the ShardingDropWarning exists for)."""
    from repro.train.serve_step import _place_batch
    model, params, batch = _quant_cell("engine_jit")
    mesh = _data_mesh(4)
    params_m = model.attach_device_plans(params, mesh=mesh)
    with jax.set_mesh(mesh):
        placed = _place_batch(batch, mesh)
        logits, caches = _jit_prefill(model, 24)(params_m, placed)
        for leaf in jax.tree_util.tree_leaves(caches["body"]):
            assert not leaf.sharding.is_fully_replicated, leaf.sharding
        tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
        analysis.assert_clean(make_decode_step(model), params_m, caches,
                              tok, jnp.int32(8), name="mesh-decode")


@needs_mesh
@pytest.mark.filterwarnings(
    "ignore::repro.distributed.sharding.ShardingDropWarning")
def test_mesh_engine_fast_path_bit_identical(cache):
    """The serve-engine fast path (Pallas live-page kernel decode +
    bucketed batched prefill) on a 4-way P('data') mesh is bit-identical
    to the 1-device per-request greedy_generate oracle. Bucket batch
    widths (1, 2, ...) need not divide the mesh extent — the resulting
    replication drop is expected on the prefill and silenced here."""
    from repro.serve import ServeEngine
    model, params, _ = _quant_cell("engine_jit")
    rng = np.random.default_rng(23)
    prompts = [rng.integers(1, model.cfg.vocab, size=n).tolist()
               for n in (3, 6, 9, 11)]          # ragged live-page counts
    max_len, gen = 16, 4
    p1 = model.attach_device_plans(params)
    refs = []
    for p in prompts:
        batch = {"tokens": jnp.asarray([p], jnp.int32)}
        refs.append(np.asarray(greedy_generate(
            model, p1, batch, max_len=max_len, n_steps=gen))[0])
    mesh = _data_mesh(4)
    eng = ServeEngine(model, model.attach_device_plans(params, mesh=mesh),
                      n_slots=4, max_len=max_len, page_size=4, mesh=mesh,
                      paged_kernel=True, bucket_prefill=True)
    for p in prompts:
        eng.submit(p, gen)
    done = eng.run()
    assert len(done) == len(prompts)
    assert eng.counters["prefill_batched_calls"] > 0
    assert eng.stats()["decode_traces"] == 1
    for r in done:
        np.testing.assert_array_equal(np.asarray(r.tokens), refs[r.rid],
                                      err_msg=f"rid={r.rid}")


@pytest.mark.slow
def test_mesh_serve_cell_subprocess():
    """The acceptance property from a 1-device host: the whole bit-exact
    comparison in a forced-4-device subprocess (test_distributed.py's
    pattern)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    code = textwrap.dedent("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.configs import get_reduced
        from repro.launch.mesh import make_serve_mesh
        from repro.launch.specs import serve_config
        from repro.models.model import Model
        from repro.train.serve_step import greedy_generate

        cfg = serve_config(get_reduced("smollm_135m").replace(n_layers=2),
                           backend="engine_jit")
        model = Model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        batch = {"tokens": jax.random.randint(
            jax.random.PRNGKey(1), (4, 8), 0, cfg.vocab, jnp.int32)}
        t1 = np.asarray(greedy_generate(
            model, model.attach_device_plans(params), batch,
            max_len=24, n_steps=5))
        mesh = make_serve_mesh("data=4")
        tn = np.asarray(greedy_generate(
            model, model.attach_device_plans(params, mesh=mesh), batch,
            max_len=24, n_steps=5, mesh=mesh))
        np.testing.assert_array_equal(t1, tn)
        print("MESH BIT-EXACT", mesh.devices.size)
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=480)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    assert "MESH BIT-EXACT 4" in r.stdout


@needs_mesh
def test_mesh_hot_swap_bit_exact_per_generation(cache):
    """ISSUE 9 on the multi-device cell: a hot swap lands mid-flight on a
    4-way data mesh; every request bit-matches the 1-DEVICE one-shot path
    on its admitting generation's weights, and decode is traced once."""
    from repro.fleet import build_generation
    from repro.serve import ServeEngine
    model, params, _ = _quant_cell("engine_jit")
    raw1 = model.init(jax.random.PRNGKey(1234))
    mesh = _data_mesh(4)
    gen0 = build_generation(model, params, gen=0, mesh=mesh)
    gen1 = build_generation(model, raw1, ref=gen0.params, gen=1, mesh=mesh)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, model.cfg.vocab, size=8).tolist()
               for _ in range(4)]
    max_len, gen_toks = 16, 4

    # 1-device references per generation (the mesh contract oracle)
    refs = {}
    for g, raw in ((0, params), (1, raw1)):
        p1 = model.attach_device_plans(raw)
        for p in prompts:
            batch = {"tokens": jnp.asarray([p], jnp.int32)}
            refs[(g, tuple(p))] = np.asarray(greedy_generate(
                model, p1, batch, max_len=max_len, n_steps=gen_toks))[0]

    eng = ServeEngine(model, gen0.params, n_slots=4, max_len=max_len,
                      page_size=4, mesh=mesh)
    with warnings.catch_warnings():
        # staggered arrivals pack < 4 rows some steps; replication is
        # bit-exact, and bit-exactness is what this test pins
        warnings.simplefilter("ignore", SH.ShardingDropWarning)
        for p in prompts[:2]:
            eng.submit(p, gen_toks)
        eng.step()                          # gen-0 requests in flight
        assert eng.swap_params(gen1.params) == 1
        submitted = 2
        while submitted < len(prompts) or eng.queue or eng.active:
            if submitted < len(prompts):
                eng.submit(prompts[submitted], gen_toks)
                submitted += 1
            eng.step()

    assert sorted({r.gen for r in eng.finished}) == [0, 1]
    for r in eng.finished:
        np.testing.assert_array_equal(
            np.asarray(r.tokens), refs[(r.gen, tuple(r.prompt))],
            err_msg=f"rid={r.rid} gen={r.gen}")
    s = eng.stats()
    assert s["decode_jit_traces"] == 1, "mesh hot swap retraced decode"
    assert eng.counters["swaps"] == 1
    assert eng.counters["generations_retired"] == 1
