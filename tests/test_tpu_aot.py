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
import os

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
    """``compile_for_chip(fn, *shapes)`` -> the compiled program. The
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
        return jax.jit(fn).lower(*placed).compile()

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
    return fn, (params, _s((SLOTS, CHUNK), jnp.int32), cache,
                _s((SLOTS, CHUNK), jnp.bool_))


@pytest.mark.parametrize("step,use_pallas", [
    ("decode_and_sample", False),
    ("fused_step_packed", False),
    ("prefill_chunk", False),
    ("prefill_chunk", True),
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
