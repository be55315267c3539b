"""ops — jit'd dispatch layer over the Pallas kernels.

Selects between the compiled TPU kernel, interpret-mode execution (CPU
correctness), and the pure-XLA oracle path; off the chip the caller must
choose. Models call these; the PAS policy's phase-aware routing
(core/pas.py ``route_fc_tpu``) decides when the GEMV kernel path replaces
the GEMM path in serving.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.pim_matvec import pim_matvec as _pim_matvec
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.decode_attention import decode_attention as _decode
from repro.kernels.masked_softmax import masked_softmax as _msoftmax
from repro.kernels.layernorm import layernorm as _layernorm
from repro.kernels.rwkv_chunk import rwkv_chunk as _rwkv_chunk


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _mode(impl: Optional[str]) -> str:
    """impl: None (auto) | 'pallas' | 'interpret' | 'xla'. Auto means the
    compiled kernel, which needs a TPU: off the chip it raises rather than
    quietly timing the XLA reference under the kernel's name."""
    if impl is not None:
        return impl
    if on_tpu():
        return "pallas"
    raise ValueError(
        f"impl=None selects the Pallas kernel, which needs a TPU (backend "
        f"is {jax.default_backend()!r}); pass impl='interpret' or 'xla'")


@functools.partial(jax.jit, static_argnames=("activation", "impl"))
def fused_matvec(x, w, bias=None, activation: str = "none",
                 impl: Optional[str] = None):
    m = _mode(impl)
    if m == "xla":
        return _ref.matvec_ref(x, w, bias, activation)
    return _pim_matvec(x, w, bias, activation, interpret=(m == "interpret"))


@functools.partial(jax.jit, static_argnames=("causal", "impl"))
def flash_attention(q, k, v, causal: bool = True, impl: Optional[str] = None):
    m = _mode(impl)
    if m == "xla":
        return _ref.flash_attention_ref(q, k, v, causal=causal)
    return _flash(q, k, v, causal=causal, interpret=(m == "interpret"))


@functools.partial(jax.jit, static_argnames=("impl",))
def decode_attention(q, k, v, lengths, impl: Optional[str] = None):
    m = _mode(impl)
    if m == "xla":
        return _ref.decode_attention_ref(q, k, v, lengths)
    return _decode(q, k, v, lengths, interpret=(m == "interpret"))


@functools.partial(jax.jit, static_argnames=("impl",))
def masked_softmax(x, mask_bitmap, impl: Optional[str] = None):
    m = _mode(impl)
    if m == "xla":
        return _ref.masked_softmax_ref(x, mask_bitmap)
    return _msoftmax(x, mask_bitmap, interpret=(m == "interpret"))


@functools.partial(jax.jit, static_argnames=("impl",))
def layernorm(x, scale, bias, impl: Optional[str] = None):
    m = _mode(impl)
    if m == "xla":
        return _ref.layernorm_ref(x, scale, bias)
    return _layernorm(x, scale, bias, interpret=(m == "interpret"))


@functools.partial(jax.jit, static_argnames=("impl",))
def rwkv_chunk(r, k, v, w, u, impl: Optional[str] = None):
    m = _mode(impl)
    if m == "xla":
        ys, ss = [], []
        for b in range(r.shape[0]):
            y, s = _ref.rwkv_chunk_ref(r[b], k[b], v[b], w[b], u[b],
                                       jnp.zeros((r.shape[2], r.shape[2]),
                                                 jnp.float32))
            ys.append(y)
            ss.append(s)
        return jnp.stack(ys), jnp.stack(ss)
    return _rwkv_chunk(r, k, v, w, u, interpret=(m == "interpret"))
