"""mamba_chunk — fused selective-scan kernel (Jamba's Mamba layers).

The XLA path (models/ssm.py) materializes per-chunk (a, u) tensors and runs
an associative scan — every intermediate round-trips HBM. The kernel keeps
the (d_tile, n) state resident in VMEM across a whole chunk and fuses the
y = C·h output contraction into the same pass: one HBM read of (a, u, C),
one write of y, state never leaves VMEM (the Mamba-official-kernel
structure, adapted to TPU VMEM tiling).

Grid: (B, d_tiles, n_chunks); chunks innermost carry the state scratch.
The default (d_tile, chunk) = (128, 32) keeps the double-buffered a/u
blocks inside v5e's 16 MiB scoped VMEM: the d_state lanes pad to 128, so a
(256, 64) tiling asks for 32 MiB and the TPU compiler refuses it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, u_ref, c_ref, y_ref, hout_ref, h_ref, *, chunk: int,
            n_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    def step(t, h):
        a_t = a_ref[:, pl.ds(t, 1)][0, 0].astype(jnp.float32)    # (dt, n)
        u_t = u_ref[:, pl.ds(t, 1)][0, 0].astype(jnp.float32)
        c_t = c_ref[:, pl.ds(t, 1)][0, 0].astype(jnp.float32)    # (n,)
        h = a_t * h + u_t
        y_t = jnp.sum(h * c_t[None, :], axis=-1)                 # (dt,)
        y_ref[:, pl.ds(t, 1)] = y_t[None, None, :].astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk, step, h_ref[...])
    h_ref[...] = h

    @pl.when(ci == n_chunks - 1)
    def _emit():
        hout_ref[0] = h_ref[...]


def mamba_chunk(a: jax.Array, u: jax.Array, C: jax.Array, *,
                d_tile: int = 128, chunk: int = 32,
                interpret: bool = False):
    """a, u: (B, T, d, n); C: (B, T, n). Returns (y (B, T, d), h_T (B, d, n)).
    h_0 = 0 (prefill semantics)."""
    B, T, d, n = a.shape
    dt = min(d_tile, d)
    c = min(chunk, T)
    assert d % dt == 0 and T % c == 0
    n_dt, n_chunks = d // dt, T // c

    # layout: (B, T, d, n) -> (B, n_dt, T, dt, n) via transpose-free blocking
    kern = functools.partial(_kernel, chunk=c, n_chunks=n_chunks)
    y, h_fin = pl.pallas_call(
        kern,
        grid=(B, n_dt, n_chunks),
        in_specs=[
            pl.BlockSpec((1, c, dt, n), lambda b, di, ci: (b, ci, di, 0)),
            pl.BlockSpec((1, c, dt, n), lambda b, di, ci: (b, ci, di, 0)),
            pl.BlockSpec((1, c, n), lambda b, di, ci: (b, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, c, dt), lambda b, di, ci: (b, ci, di)),
            pl.BlockSpec((1, dt, n), lambda b, di, ci: (b, di, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, T, d), a.dtype),
            jax.ShapeDtypeStruct((B, d, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dt, n), jnp.float32)],
        interpret=interpret,
    )(a, u, C)
    return y, h_fin
