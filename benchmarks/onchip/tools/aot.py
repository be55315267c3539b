"""Compile a configuration's serving programs for a described v5e chip,
without the chip, and print their memory analysis.

    JAX_PLATFORMS=cpu python3 benchmarks/onchip/tools/aot.py olmo-1b

Compiles the engine's ``decode_and_sample`` and its ``prefill_chunk`` at
the last chunk offset of ``max_len``, at the configuration's serve sizes,
and, where the configuration's architecture module has a
``reference_layer``, the reference's layer, float32 and the fp8 control,
at the check's sizes for each mix given after the configuration name.
"""
from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

import cells  # noqa: E402
import system  # noqa: E402
import weights  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.models.params import abstract_params  # noqa: E402
from repro.serve import engine as E  # noqa: E402


def gib(n):
    return f"{n / 2**30:.2f} GiB"


def report(label, compiled):
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    print(f"{label}: arguments {gib(m.argument_size_in_bytes)}, outputs "
          f"{gib(m.output_size_in_bytes)}, temporaries "
          f"{gib(m.temp_size_in_bytes)}, aliased {gib(m.alias_size_in_bytes)}"
          f"; total {gib(total)}", flush=True)


def main(name: str, mixes) -> None:
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        conf = json.load(f)
    arch = cells.arch(conf)
    cfg = system.model_config(conf, arch)
    scfg = system.serve_config(conf)
    B, L, C = scfg.max_slots, scfg.max_len, scfg.prefill_chunk

    def sds(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)

    params = sds(abstract_params(T.param_defs(cfg)))
    cache = sds(abstract_params(T.cache_defs(cfg, B, L)))
    i32 = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one)
    act = jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=one)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one)
    dec = E._jit_decode_sample(cfg, scfg.temperature, scfg.eos_token, L)
    report(f"{name} decode_and_sample {B}x{L}",
           dec.lower(params, cache, i32, i32, act, i32, i32, rng).compile())
    off = (L // C - 1) * C
    toks = jax.ShapeDtypeStruct((B, C), jnp.int32, sharding=one)
    valid = jax.ShapeDtypeStruct((B, C), jnp.bool_, sharding=one)
    pre = E._jit_prefill(cfg, off)
    report(f"{name} prefill_chunk offset {off} {B}x{C}",
           pre.lower(params, toks, cache, valid).compile())
    if not hasattr(arch, "reference_layer"):
        return
    w = weights.abstract(arch.shapes(conf), one)
    for mix in mixes:
        with open(os.path.join(HERE, "traffic", f"{mix}.json")) as f:
            rows = json.load(f)["check"]["requests"]
        for mode in ("f32", "fp8"):
            report(f"{name} reference layer ({mix}, {rows}x{L}, {mode})",
                   arch.reference_layer(conf, w, rows, L, mode).compile())


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2:])
