"""Compile the serving path's device programs for a TPU v5e, at the full
published width of smollm-135m, without a chip.

The TPU compiler is installed with jaxlib and compiles for a described,
unattached topology. It refuses what interpret mode accepts: unaligned
blocks, kernels that need more fast memory than a core has, programs that
do not fit the device. Nothing here runs, so these tests say nothing about
results or time; ``chip_smoke.py`` runs the same programs on a chip.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and pytest-xdist workers all
import this file.
"""
import functools
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.launch.specs import serve_config
from repro.models.model import Model

HBM_BYTES = 16 * 2**30          # one v5e chip
SLOTS, MAX_LEN, PAGE = 8, 2048, 16
PREFILL_ROWS, PREFILL_LEN = 8, 256
PREFIX_PAGES = 4


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A program compiled for a described chip is written to the
    persistent cache but cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def served(one_chip, no_compile_cache):
    """Full-width smollm-135m as ``launch/serve.py`` builds it, as shapes
    placed on one described v5e chip, with an engine-sized page pool."""
    cfg = serve_config(get_config("smollm-135m"), w_bits=4,
                       backend="int_dot")
    model = Model(cfg)

    def place(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = place(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    n_pages = SLOTS * MAX_LEN // PAGE + 1      # ServeEngine's default
    pool = place(jax.eval_shape(
        functools.partial(model.init_page_pool, n_pages, PAGE)))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    return model, params, pool, i32


def _check_fits(compiled):
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 0 < used < HBM_BYTES, used


def _prefill(served, n_pre: int):
    """One bucketed batched-prefill program, 8 rows x 256 positions
    behind ``n_pre`` shared prefix pages, pool donated."""
    model, params, pool, i32 = served
    prefill = jax.jit(model.prefill_paged_batched, donate_argnums=(2,))
    rows, lb = PREFILL_ROWS, PREFILL_LEN
    return prefill.lower(
        params, i32(rows, lb), pool, prefix_page_ids=i32(rows, n_pre),
        prefix_lens=i32(rows), suffix_lens=i32(rows),
        write_page_ids=i32(rows, lb), write_offs=i32(rows, lb),
        write_pos=i32(rows, lb)).compile()


@pytest.fixture(scope="module")
def compiled(served):
    """Each served program compiled once for the tests below."""
    model, params, pool, i32 = served
    decode = jax.jit(model.decode_step_paged, static_argnames=("kernel",),
                     donate_argnums=(1,))
    return {"decode": decode.lower(params, pool, i32(SLOTS, 1),
                                   i32(SLOTS, MAX_LEN // PAGE), i32(SLOTS),
                                   kernel=False).compile(),
            "prefill": _prefill(served, 0),
            "prefill_shared": _prefill(served, PREFIX_PAGES)}


def test_paged_decode_compiles_for_v5e(compiled):
    """The packed decode ``ServeEngine`` jits: gather path, ``int_dot``,
    8 slots x 2048 positions, pool donated."""
    _check_fits(compiled["decode"])


def test_bucketed_prefill_compiles_for_v5e(compiled):
    """One bucketed batched-prefill program: 8 rows x 256 positions, no
    shared prefix, pool donated."""
    _check_fits(compiled["prefill"])


_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (\w+)\[([\d,]*)\]"
                    r"\S* ([\w\-]+)\(([^)]*)\)", re.M)
_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
             "u16": 2, "f32": 4, "s32": 4, "u32": 4}


def _pool_moves(text: str, pool) -> list[str]:
    """Instructions that move pool-sized data: a ``copy`` or
    ``dynamic-slice`` whose result, or a ``dynamic-update-slice`` whose
    update, has a page-count dimension and is at least one layer's
    smallest pool leaf. Fusion bodies are searched too."""
    leaves = jax.tree.leaves(pool)
    n_pages = leaves[0].shape[1]
    least = min(math.prod(a.shape[1:]) * a.dtype.itemsize for a in leaves)
    shapes = {m[0]: (m[1], [int(d) for d in m[2].split(",") if d])
              for m in _INSTR.findall(text)}
    out = []
    for name, dt, dims, op, args in _INSTR.findall(text):
        if op == "dynamic-update-slice":
            update = args.split(",")[1].strip().lstrip("%")
            dt, dims = shapes.get(update, (dt, []))
        elif op in ("copy", "dynamic-slice"):
            dims = [int(d) for d in dims.split(",") if d]
        else:
            continue
        nbytes = math.prod(dims) * _ITEMSIZE.get(dt, 4)
        if n_pages in dims and nbytes >= least:
            out.append(f"{name} {op} {dt}{dims}")
    return out


@pytest.mark.parametrize("program", ["decode", "prefill", "prefill_shared"])
def test_pool_updated_in_place(served, compiled, program):
    """The layer scan carries the stacked pool and updates it in place:
    no leaf or layer slice of it is copied, relaid out or written back
    whole, and the program's temporaries stay below one stacked pool."""
    pool = served[2]
    prog = compiled[program]
    text = prog.as_text()
    n_pages = jax.tree.leaves(pool)[0].shape[1]
    assert any(f",{n_pages}," in f",{dims}," for _, _, dims, _, _
               in _INSTR.findall(text)), "the pool's instructions parse"
    assert _pool_moves(text, pool) == []
    stacked = sum(math.prod(a.shape) * a.dtype.itemsize
                  for a in jax.tree.leaves(pool))
    assert prog.memory_analysis().temp_size_in_bytes < stacked


@pytest.mark.parametrize("program,scopes", [
    ("decode", {"linear", "kv_gather", "attention"}),
    # a KV8 pool recomputes shared prefixes, so its prefill gathers none
    ("prefill", {"linear", "attention"}),
    # the bucket an exact-pool engine sends behind a shared prefix
    ("prefill_shared", {"linear", "kv_gather", "attention"}),
], ids=["decode", "prefill", "prefill_shared"])
def test_served_programs_carry_layer_scopes(compiled, program, scopes):
    """The layer scopes reach the compiled program's instructions, whose
    ``op_name`` a device trace reports as each op's ``tf_op``."""
    names = re.findall(r'op_name="([^"]*)"', compiled[program].as_text())
    found = {part for n in names for part in n.split("/")}
    assert found & {"linear", "kv_gather", "attention"} == scopes
