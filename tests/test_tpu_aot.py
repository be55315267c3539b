"""Ahead-of-time compiles for one described TPU v5e chip.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached, so these tests need no accelerator. They refuse what
the chip would refuse and interpret mode never checks: block shapes off the
(8, 128) tiling, kernels that overrun VMEM, programs that do not fit HBM.
Nothing runs, so they say nothing about results or times.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import dataclasses
import functools
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mamba_chunk import mamba_chunk
from repro.kernels.pim_matvec import pim_matvec
from repro.models import transformer as T
from repro.models.params import abstract_params
from repro.serve import engine

V5E_HBM_BYTES = 16 * 2**30
SLOTS, MAX_LEN, CHUNK = 8, 2048, 256          # chip_smoke.py's serving shape


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler here: nothing to test
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def compile_for_chip(one_chip):
    """``compile_for_chip(fn, *shapes)`` -> the compiled program; a
    function that is already jitted keeps its own options (donation). The
    persistent compilation cache is off meanwhile: an entry compiled for a
    described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def compile_(fn, *shapes):
        placed = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip), shapes)
        jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
        return jitted.lower(*placed).compile()

    yield compile_
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _s(shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


# olmo-1b attention widths: 16 heads of 128 (MHA), a 256-token prefill chunk
# whose prefix spans one chunk before it
B, H, D = SLOTS, 16, 128


@pytest.mark.parametrize("mode", ["q_offset", "segmented"])
def test_flash_attention_compiles(compile_for_chip, mode):
    q, kv = _s((B, H, CHUNK, D)), _s((B, H, 2 * CHUNK, D))
    if mode == "q_offset":
        fn = functools.partial(flash_attention, block_q=CHUNK,
                               block_kv=CHUNK, q_offset=CHUNK)
        compiled = compile_for_chip(fn, q, kv, kv)
    else:
        def fn(q, k, v, q_pos, q_seg, kv_pos, kv_seg):
            return flash_attention(q, k, v, block_q=CHUNK, block_kv=CHUNK,
                                   segment_info=(q_pos, q_seg, kv_pos,
                                                 kv_seg))
        qi, ki = _s((B, CHUNK), jnp.int32), _s((B, 2 * CHUNK), jnp.int32)
        compiled = compile_for_chip(fn, q, kv, kv, qi, qi, ki, ki)
    assert _has_kernel(compiled)


def test_decode_attention_compiles(compile_for_chip):
    cache = _s((B, H, MAX_LEN, D))
    compiled = compile_for_chip(decode_attention, _s((B, H, D)), cache,
                                cache, _s((B,), jnp.int32))
    assert _has_kernel(compiled)


def test_pim_matvec_compiles(compile_for_chip):
    fn = functools.partial(pim_matvec, bias=None, activation="none")
    compiled = compile_for_chip(fn, _s((B, 2048)), _s((2048, 8192)))
    assert _has_kernel(compiled)


def test_mamba_chunk_compiles(compile_for_chip):
    """Jamba's Mamba widths (d_inner 8192, d_state 16) at the default
    tiling, which must fit v5e's scoped VMEM."""
    a = _s((1, 512, 8192, 16), jnp.float32)
    compiled = compile_for_chip(mamba_chunk, a, a,
                                _s((1, 512, 16), jnp.float32))
    assert _has_kernel(compiled)


def _serving_args(cfg, step):
    params = abstract_params(T.param_defs(cfg))
    cache = abstract_params(T.cache_defs(cfg, SLOTS, MAX_LEN))
    i32 = _s((SLOTS,), jnp.int32)
    decode = (i32, i32, _s((SLOTS,), jnp.bool_), i32, i32,
              _s((2,), jnp.uint32))
    sampling = dict(temperature=0.0, eos_token=None, max_len=MAX_LEN)
    if step == "decode_and_sample":
        fn = functools.partial(T.decode_and_sample, cfg, **sampling)
        return fn, (params, cache) + decode
    if step == "fused_step_packed":
        # a packed chunk of SLOTS rows riding the resident batch's decode
        fn = functools.partial(T.fused_step_packed, cfg, prefix_span=CHUNK,
                               **sampling)
        rows = _s((SLOTS, CHUNK), jnp.int32)
        return fn, (params, cache, rows, rows, rows, rows,
                    _s((SLOTS, CHUNK), jnp.bool_), i32, i32) + decode
    fn = functools.partial(T.prefill_chunk, cfg, offset=CHUNK)
    if step == "prefill_row_grid":
        # one row that names its slot
        return fn, (params, _s((1, CHUNK), jnp.int32), cache,
                    _s((1, CHUNK), jnp.bool_), _s((1,), jnp.int32))
    return fn, (params, _s((SLOTS, CHUNK), jnp.int32), cache,
                _s((SLOTS, CHUNK), jnp.bool_))


@pytest.mark.parametrize("step,use_pallas", [
    ("decode_and_sample", False),
    ("fused_step_packed", False),
    ("prefill_chunk", False),
    ("prefill_chunk", True),
    ("prefill_row_grid", False),
    ("prefill_row_grid", True),
])
def test_olmo_serving_step_fits_one_chip(compile_for_chip, step, use_pallas):
    """The served olmo-1b step programs at full width (8 slots x 2048):
    arguments plus temporaries plus outputs fit one v5e's HBM, and the
    Pallas prefill really holds the kernel."""
    cfg = dataclasses.replace(get_arch("olmo-1b"), use_pallas=use_pallas)
    fn, args = _serving_args(cfg, step)
    compiled = compile_for_chip(fn, *args)
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes)
    assert total < V5E_HBM_BYTES, (step, total / 2**30)
    assert _has_kernel(compiled) == use_pallas


def _engine_program(cfg, step):
    """The engine's own jitted ``step`` program, with its donation, and its
    arguments at the serving shape: (fn, args, cache, weights)."""
    params = abstract_params(T.param_defs(cfg))
    cache = abstract_params(T.cache_defs(cfg, SLOTS, MAX_LEN))
    i32 = _s((SLOTS,), jnp.int32)
    if step == "decode_and_sample":
        fn = engine._jit_decode_sample(cfg, 0.0, None, MAX_LEN)
        args = (params, cache, i32, i32, _s((SLOTS,), jnp.bool_), i32, i32,
                _s((2,), jnp.uint32))
    elif step == "prefill_chunk":
        fn = engine._jit_prefill(cfg, CHUNK)
        args = (params, _s((SLOTS, CHUNK), jnp.int32), cache,
                _s((SLOTS, CHUNK), jnp.bool_))
    elif step == "prefill_row_grid":
        # the same program over the one row of a 256-token chunk that
        # names its slot: what the engine dispatches at this chunk
        fn = engine._jit_prefill(cfg, CHUNK)
        args = (params, _s((1, CHUNK), jnp.int32), cache,
                _s((1, CHUNK), jnp.bool_), _s((1,), jnp.int32))
    else:
        fn, args, params = engine.reset_slots, (cache, i32), {}
    return fn, args, cache, params


def _nbytes(tree) -> int:
    return sum(math.prod(a.shape) * a.dtype.itemsize
               for a in jax.tree.leaves(tree))


# one HLO instruction: its name, single-array result type and opcode
_INSTR = re.compile(r"^\s*(?:ROOT )?%\S+ = (\w+)\[([\d,]*)\]\S* ([\w-]+)\(")


def _whole_cache_writers(hlo: str, leaf_elems: int):
    """Instructions outside fusion bodies whose result has as many elements
    as a cache leaf and is neither a view (parameter, get-tuple-element,
    bitcast) nor a fusion that updates an operand in place."""
    bad, in_fusion = [], False
    for line in hlo.splitlines():
        if line.endswith("{") and "->" in line:
            in_fusion = line.lstrip("%").startswith("fused")
            continue
        m = _INSTR.match(line)
        if in_fusion or m is None:
            continue
        dims = [int(d) for d in m.group(2).split(",") if d]
        if math.prod(dims) != leaf_elems:
            continue
        op = m.group(3)
        if op in ("parameter", "get-tuple-element", "bitcast"):
            continue
        if op == "fusion" and '"aliasing_operands"' in line:
            continue
        bad.append(line.strip()[:160])
    return bad


@pytest.mark.parametrize("step", ["decode_and_sample", "prefill_chunk",
                                  "prefill_row_grid", "reset_slots"])
def test_olmo_step_updates_cache_in_place(compile_for_chip, step):
    """The engine's olmo-1b decode, prefill and admission-reset programs
    at full width donate the KV cache and write into it in place: the
    whole cache aliases an output, temporaries stay under one layer's K+V,
    no instruction writes a fresh whole-cache-sized buffer, and the
    program holds the weights plus ONE cache, not two."""
    cfg = get_arch("olmo-1b")
    fn, args, cache, params = _engine_program(cfg, step)
    compiled = compile_for_chip(fn, *args)
    mem = compiled.memory_analysis()
    cache_b, weights_b = _nbytes(cache), _nbytes(params)
    assert mem.alias_size_in_bytes >= cache_b, (mem.alias_size_in_bytes,
                                                cache_b)
    assert mem.temp_size_in_bytes < cache_b // cfg.num_layers, \
        mem.temp_size_in_bytes
    leaf = cache["pos0"]["k"]
    assert _whole_cache_writers(compiled.as_text(),
                                math.prod(leaf.shape)) == []
    held = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert abs(held - (weights_b + cache_b)) < 0.03 * (weights_b + cache_b), \
        (held / 2**30, (weights_b + cache_b) / 2**30)


def test_olmo_row_grid_prefill_holds_less(compile_for_chip):
    """The engine's olmo-1b prefill over one row naming its slot compiles
    for one v5e beside the slot grid's, and holds less temporary memory."""
    cfg = get_arch("olmo-1b")
    assert engine.prefill_rows(SLOTS, CHUNK) == 1
    temp = {}
    for step in ("prefill_chunk", "prefill_row_grid"):
        fn, args, _, _ = _engine_program(cfg, step)
        temp[step] = compile_for_chip(fn, *args).memory_analysis() \
            .temp_size_in_bytes
    assert temp["prefill_row_grid"] < temp["prefill_chunk"], temp
