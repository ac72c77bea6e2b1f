"""Compile a cell's programs for a described TPU v5e and print their memory.

    JAX_PLATFORMS=cpu python3 -m bench.rehearse <cell> [--blocks N ...]

Run by hand, with no chip. For each pool size (the cell's
``num_blocks``, or each ``--blocks``), it compiles the cell's paged
decode step and its largest prefill bucket (and, for a mix with system
prompts, the prefix-hit prefill of the longest turn) at the published
widths, and prints ``memory_analysis()`` with the sum a process holds
at the decode step's peak: the weights and the pool it keeps, the new
pool the step returns beside them (nothing donates the old one), and
the step's temporaries. The pool size each cell runs at comes from this
output; ``PERF.md`` records it.
"""
from __future__ import annotations

import argparse
import os
import sys

from bench import spec, traffic
from bench.run import program_config

GiB = 2**30
V5E_HBM = 16 * GiB


def describe_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    return SingleDeviceSharding(topo.devices[0])


def mem(compiled) -> dict:
    m = compiled.memory_analysis()
    return {
        "args": m.argument_size_in_bytes,
        "out": m.output_size_in_bytes,
        "temp": m.temp_size_in_bytes,
        "alias": m.alias_size_in_bytes,
        "code": m.generated_code_size_in_bytes,
    }


def show(name: str, m: dict) -> None:
    print(
        f"  {name:<18} args {m['args'] / GiB:7.3f} GiB  out {m['out'] / GiB:7.3f} GiB  "
        f"temp {m['temp'] / GiB:7.3f} GiB  alias {m['alias'] / GiB:6.3f} GiB",
        flush=True,
    )


def rehearse(cell_name: str, blocks: list[int]) -> None:
    import jax
    import jax.numpy as jnp

    spec.use_program()
    from repro.models.model import Model

    one = describe_chip()
    cell = spec.load_cell(cell_name)
    conf = spec.load_config(cell["config"])
    mix = spec.load_mix(cell["traffic"])
    pcfg, sizes = program_config(conf)
    model = Model(pcfg)
    B, S, bs = int(cell["max_batch"]), int(cell["max_seq"]), int(cell["block_size"])

    def sds(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one), tree)

    params = sds(model.abstract_params()[0])
    wbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    print(f"{cell_name}: weights {wbytes / GiB:.3f} GiB, max_batch {B}, max_seq {S}", flush=True)

    p = mix["prompt"]
    longest = p["hi"] + (mix["system_prompts"]["length"] if mix.get("system_prompts") else 0)
    W = traffic.pow2_buckets(longest, longest)[0]
    prefill = jax.jit(lambda pr, t, **kw: model.prefill(pr, t, S, **kw))
    c = prefill.lower(
        params, jax.ShapeDtypeStruct((1, W), jnp.int32, sharding=one),
        prompt_len=jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one),
    ).compile()
    show(f"prefill W={W}", mem(c))
    if mix.get("system_prompts"):
        h = mix["system_prompts"]["length"]
        Ws = traffic.pow2_buckets(p["hi"], p["hi"])[0]
        kv = jax.ShapeDtypeStruct(
            (pcfg.num_layers, 1, h, pcfg.n_kv_heads, pcfg.head_dim), jnp.dtype(pcfg.dtype), sharding=one
        )
        pp = jax.jit(lambda pr, t, pk, pv, **kw: model.prefill_with_prefix(pr, t, pk, pv, S, **kw))
        c = pp.lower(
            params, jax.ShapeDtypeStruct((1, Ws), jnp.int32, sharding=one), kv, kv,
            suffix_len=jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one),
        ).compile()
        show(f"prefix W={Ws} h={h}", mem(c))

    decode = model.jit_step("decode_step_paged", "kernel")
    for nb in blocks:
        pool = sds(jax.eval_shape(lambda nb=nb: model.init_paged_cache(nb + 1, bs)))
        pbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(pool))
        try:
            c = decode.lower(
                params, pool,
                jax.ShapeDtypeStruct((B, S // bs), jnp.int32, sharding=one),
                jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one),
                jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=one),
            ).compile()
        except Exception as e:  # the compiler refuses what does not fit
            print(f" num_blocks {nb}: pool {pbytes / GiB:.3f} GiB: {str(e).splitlines()[0]}")
            continue
        m = mem(c)
        peak = m["args"] + m["out"] + m["temp"] - m["alias"]
        print(
            f" num_blocks {nb} ({nb * bs} tokens): pool {pbytes / GiB:.3f} GiB, "
            f"args+out+temp {peak / GiB:.3f} GiB (the compiler, which accepted it, reuses some of that)",
            flush=True,
        )
        show("decode", m)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell")
    ap.add_argument("--blocks", type=int, nargs="*")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.cell)
    default = cell.get("num_blocks") or cell["max_batch"] * cell["max_seq"] // cell["block_size"]
    rehearse(args.cell, args.blocks or [default])
    return 0


if __name__ == "__main__":
    sys.exit(main())
