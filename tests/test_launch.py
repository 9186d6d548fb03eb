"""Launcher-layer units: collective parser, roofline terms, shape specs,
skip rules, analytic flops — all pure (no 512-device init needed)."""
import jax
import pytest

from repro.configs import ARCHS, get_config
from repro.configs.base import SHAPES
from repro.launch.roofline import (HW, collective_bytes, model_flops,
                                   roofline_terms)
from repro.launch.dryrun import DRYRUN_ARCHS, cell_skip_reason


def test_collective_parser():
    hlo = """
  %ar = f32[1024]{0} all-reduce(f32[1024]{0} %x), replica_groups={}
  %ag = bf16[16,256]{1,0} all-gather(bf16[16,16]{1,0} %y), dimensions={1}
  %rs = f32[64]{0} reduce-scatter(f32[1024]{0} %z), dimensions={0}
  %cp = s8[128]{0} collective-permute(s8[128]{0} %w)
  %no = f32[4]{0} add(f32[4]{0} %a, f32[4]{0} %b)
"""
    cb = collective_bytes(hlo)
    assert cb["all-reduce"] == 2.0 * 1024 * 4          # 2x ring factor
    assert cb["all-gather"] == 16 * 256 * 2            # result bytes
    assert cb["reduce-scatter"] == 1024 * 4            # operand bytes
    assert cb["collective-permute"] == 128
    assert cb["count"] == 4
    assert cb["total"] == sum((cb["all-reduce"], cb["all-gather"],
                               cb["reduce-scatter"], cb["all-to-all"],
                               cb["collective-permute"],
                               cb["ragged-all-to-all"]))


def test_roofline_terms_dominance():
    t = roofline_terms(HW["peak_flops"], 0.0, 0.0)
    assert t["dominant"] == "compute" and t["t_compute_s"] == 1.0
    assert t["roofline_fraction"] == 1.0
    t = roofline_terms(1.0, HW["hbm_bw"], 0.0)
    assert t["dominant"] == "memory"
    t = roofline_terms(1.0, 1.0, HW["link_bw"] * 2)
    assert t["dominant"] == "collective"


@pytest.mark.parametrize("arch", DRYRUN_ARCHS)
def test_model_flops_positive_all_cells(arch):
    cfg = get_config(arch)
    for shape in SHAPES.values():
        f = model_flops(cfg, shape)
        assert f > 0
        if shape.kind == "train":
            # 6ND lower bound (attention terms only add)
            assert f >= 5.9 * 1e6 * shape.global_batch


def test_skip_rules():
    assert cell_skip_reason(get_config("qwen3_14b"),
                            SHAPES["long_500k"]) is not None
    assert cell_skip_reason(get_config("recurrentgemma_9b"),
                            SHAPES["long_500k"]) is None
    assert cell_skip_reason(get_config("xlstm_125m"),
                            SHAPES["long_500k"]) is None
    for arch in DRYRUN_ARCHS:
        assert cell_skip_reason(get_config(arch), SHAPES["train_4k"]) is None
    assert len(DRYRUN_ARCHS) == 10 and len(ARCHS) == 11


def test_effective_accum_caps_to_dp():
    from repro.launch.specs import effective_accum
    from repro.launch.mesh import make_local_mesh
    cfg = get_config("llama4_maverick_400b_a17b")     # grad_accum=16
    mesh = make_local_mesh(1, 1)
    # pretend meshes via duck shape dicts is brittle — use the real one:
    assert effective_accum(cfg, SHAPES["train_4k"], mesh) == 16
    # on a 2-wide data mesh, 256/(16*2)=8 microbatches of 16 still fit
    mesh2 = make_local_mesh(2 if jax.device_count() >= 2 else 1, 1)
    a = effective_accum(cfg, SHAPES["train_4k"], mesh2)
    assert SHAPES["train_4k"].global_batch % a == 0


def test_serve_config_flags():
    from repro.launch.specs import serve_config
    scfg = serve_config(get_config("qwen3_14b"))
    assert scfg.quant.mode == "ptq" and scfg.quant.w_bits == 4
    assert scfg.quant_attention and scfg.kv_cache_bits == 8
    w = serve_config(get_config("whisper_tiny"))
    assert not w.quant_attention and w.kv_cache_bits == 16


def test_param_specs_shapes_align():
    """Every param leaf gets a spec of matching rank (no mesh needed)."""
    from repro.distributed.sharding import param_specs
    from repro.models.model import Model
    cfg = get_config("moonshot_v1_16b_a3b").replace(n_layers=1)
    shapes = jax.eval_shape(lambda: Model(cfg).init(jax.random.PRNGKey(0)))
    specs = param_specs(shapes)
    flat_s = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: hasattr(x, "_normalized_spec") or
        x.__class__.__name__ == "PartitionSpec")
    flat_p = jax.tree_util.tree_leaves(shapes)
    assert len(flat_s) == len(flat_p)
    for sp, p in zip(flat_s, flat_p):
        assert len(sp) <= p.ndim


@pytest.fixture
def cache_dir_config():
    """Restore JAX's compile-cache directory after the test."""
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


@pytest.mark.parametrize("env", [None, "outside"])
def test_compile_cache_placement(env, monkeypatch, tmp_path,
                                 cache_dir_config):
    """The entry points' compile cache: where JAX_COMPILATION_CACHE_DIR
    is set, JAX has read it and nothing overrides it; otherwise a fixed
    <checkout>/.jax_cache that git ignores."""
    import pathlib
    import subprocess
    from repro.launch.compile_cache import (DEFAULT_CACHE_DIR,
                                            enable_compile_cache)
    root = pathlib.Path(__file__).resolve().parents[1]
    assert DEFAULT_CACHE_DIR == root / ".jax_cache"
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert enable_compile_cache() == str(DEFAULT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(DEFAULT_CACHE_DIR)
        ignored = subprocess.run(
            ["git", "check-ignore", "-q", str(DEFAULT_CACHE_DIR / "x")],
            cwd=root, check=False)
        assert ignored.returncode in (0, 128), "git must ignore .jax_cache"
    else:
        outside = str(tmp_path / env)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
        jax.config.update("jax_compilation_cache_dir", outside)  # as read
        assert enable_compile_cache() == outside
        assert jax.config.jax_compilation_cache_dir == outside
