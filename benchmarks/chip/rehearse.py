#!/usr/bin/env python3
"""Compile a cell's programs for a described TPU v5e, with no chip.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse.py [--slots N] \
        [cell ...]

For each cell (all of ``BENCHMARK.json`` by default; ``--slots`` in
place of the configuration's slot count) it compiles, for one
chip of a described ``v5e:2x2``: the weight build, the packed decode, and
every batched prefill bucket (batch bucket x length bucket) that the
cell's mix and slot count can ask for, and the reference's
``layer_step`` at its largest sample (a reference without one is named
as not compiled). It prints ``memory_analysis()`` of each: arguments,
outputs, temporaries, and the sum of arguments and temporaries against
the chip's 16 GiB. The compiler refuses here what it would refuse there.
"""
from __future__ import annotations

import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax                                              # noqa: E402
import jax.numpy as jnp                                 # noqa: E402

import run                                              # noqa: E402
from traffic import sizes                               # noqa: E402

GIB = 2 ** 30


def main(cells, slots: int = 0) -> int:
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from repro.serve import bucket
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def spec(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
            tree)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)

    def report(what, fn, *args, **kw):
        t = time.perf_counter()
        m = fn.lower(*args, **kw).compile().memory_analysis()
        a, o, tmp = (m.argument_size_in_bytes, m.output_size_in_bytes,
                     m.temp_size_in_bytes)
        print(f"| {what} | {a / GIB:.2f} | {o / GIB:.2f} | {tmp / GIB:.2f} "
              f"| {(a + tmp) / GIB:.2f} | {time.perf_counter() - t:.1f} |",
              flush=True)

    print("| program | args GiB | out GiB | temp GiB | args+temp GiB "
          "| compile s |\n|---|---|---|---|---|---|")
    done = set()
    for name in cells:
        files = run.cell_files(name)
        conf, mix = files["conf"], files["traffic"]
        dims = run.dims_of(conf)
        bits = conf["program"]["serve"]["w_bits"]
        ref = run.reference(files)
        model = run.program(conf, dims, ref)
        e = {**conf["engine"], **({"n_slots": slots} if slots else {})}
        tag = f"{conf['name']} ({e['n_slots']} slots)"
        build = jax.jit(ref.builder(dims, bits))
        key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one)
        params = spec(jax.eval_shape(build, jax.random.PRNGKey(0)))
        n_pages = e["n_slots"] * e["max_len"] // e["page_size"] + 1
        pool = spec(jax.eval_shape(
            lambda: model.init_page_pool(n_pages, e["page_size"])))
        if (tag, "build") not in done:
            done.add((tag, "build"))
            report(f"{tag} weight build", build, key)
            decode = jax.jit(model.decode_step_paged,
                             static_argnames=("kernel",),
                             donate_argnums=(1,))
            n, pps = e["n_slots"], e["max_len"] // e["page_size"]
            report(f"{tag} decode", decode, params, pool,
                   i32(n, 1), i32(n, pps), i32(n), kernel=False)
        prefill = jax.jit(model.prefill_paged_batched, donate_argnums=(2,))
        lo, hi = sizes.prompt_lengths(mix)
        nbs = sorted({bucket(g, e["n_slots"])
                      for g in range(1, e["n_slots"] + 1)})
        lbs = sorted({bucket(x, e["max_len"]) for x in range(lo, hi + 1)})
        for lb in lbs:
            for nb in nbs:
                if (tag, nb, lb) in done:
                    continue
                done.add((tag, nb, lb))
                report(f"{tag} prefill {nb}x{lb}", prefill, params,
                       i32(nb, lb), pool, prefix_page_ids=i32(nb, 0),
                       prefix_lens=i32(nb), suffix_lens=i32(nb),
                       write_page_ids=i32(nb, lb), write_offs=i32(nb, lb),
                       write_pos=i32(nb, lb))
        s_pad = run.ref_length(mix)
        if (tag, "ref", s_pad) not in done:
            done.add((tag, "ref", s_pad))
            if not hasattr(ref, "layer_step"):
                print(f"| {tag} reference layer | not compiled: "
                      f"{files['reference'].name} has no layer_step |")
                continue
            x = jax.ShapeDtypeStruct((run.SAMPLE_ROWS, s_pad,
                                      dims["d_model"]), jnp.float32,
                                     sharding=one)
            report(f"{tag} reference layer {run.SAMPLE_ROWS}x{s_pad}",
                   ref.layer_step, x, key,
                   jax.ShapeDtypeStruct((), jnp.int32, sharding=one),
                   dims=tuple(sorted(dims.items())), bits=bits, lowbits=0)
    return 0


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--slots", type=int, default=0)
    ap.add_argument("cells", nargs="*")
    args = ap.parse_args()
    sys.exit(main(args.cells or [w["name"] for w in
                                 run.load(run.ROOT / "BENCHMARK.json")
                                 ["workloads"]], args.slots))
