"""Per-kernel validation: sweep shapes/dtypes, assert_allclose vs ref.py
(pure-jnp oracles). Kernels run in interpret mode on CPU (the TPU target
path is identical BlockSpec code)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.pim_matvec import pim_matvec
from repro.kernels.flash_attention import flash_attention
from repro.kernels.decode_attention import decode_attention
from repro.kernels.masked_softmax import masked_softmax
from repro.kernels.layernorm import layernorm
from repro.kernels.rwkv_chunk import rwkv_chunk

KEY = jax.random.PRNGKey(0)


def _tol(dtype):
    return dict(rtol=5e-2, atol=5e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("n,d_in,d_out,act,bias", [
    (1, 256, 512, "none", False),
    (1, 1024, 1024, "gelu", True),
    (4, 512, 256, "silu", True),
    (8, 2048, 512, "gelu", False),
    (16, 256, 1024, "none", True),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pim_matvec(n, d_in, d_out, act, bias, dtype):
    k1, k2, k3 = jax.random.split(KEY, 3)
    x = (jax.random.normal(k1, (n, d_in)) * 0.5).astype(dtype)
    w = (jax.random.normal(k2, (d_in, d_out)) * 0.02).astype(dtype)
    b = jax.random.normal(k3, (d_out,)).astype(dtype) if bias else None
    got = pim_matvec(x, w, b, act, block_n=256, block_k=256, interpret=True)
    want = ref.matvec_ref(x, w, b, act)
    np.testing.assert_allclose(got.astype(np.float32),
                               want.astype(np.float32), **_tol(dtype))


@pytest.mark.parametrize("B,H,KH,S,D,causal", [
    (1, 4, 4, 64, 32, True),
    (2, 4, 2, 128, 64, True),
    (2, 8, 1, 128, 64, False),   # MQA
    (1, 8, 2, 256, 128, True),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(B, H, KH, S, D, causal, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, S, D)).astype(dtype)
    k = jax.random.normal(ks[1], (B, KH, S, D)).astype(dtype)
    v = jax.random.normal(ks[2], (B, KH, S, D)).astype(dtype)
    got = flash_attention(q, k, v, causal=causal, block_q=32, block_kv=64,
                          interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(got.astype(np.float32),
                               want.astype(np.float32), rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("B,H,KH,Sq,Skv,offset", [
    (1, 4, 2, 32, 64, 32),     # chunk 1 of a 2-chunk prefill
    (2, 4, 4, 64, 192, 128),   # chunk 2 of 3
    (1, 8, 2, 32, 32, 0),      # degenerate: plain causal self-attn
])
def test_flash_attention_q_offset(B, H, KH, Sq, Skv, offset):
    """Chunked-prefill masking: queries at global positions
    [offset, offset+Sq) against KV [0, Skv) must equal the corresponding
    row-block of full causal attention."""
    D = 32
    ks = jax.random.split(KEY, 3)
    q_full = jax.random.normal(ks[0], (B, H, Skv, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, KH, Skv, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, KH, Skv, D), jnp.float32)
    q = q_full[:, :, offset:offset + Sq]
    got = flash_attention(q, k, v, causal=True, block_q=16, block_kv=32,
                          q_offset=offset, interpret=True)
    want = ref.flash_attention_ref(q_full, k, v, causal=True
                                   )[:, :, offset:offset + Sq]
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("B,H,KH,S,D", [
    (2, 8, 2, 256, 64),
    (1, 4, 4, 128, 32),
    (3, 4, 1, 512, 64),
])
def test_decode_attention(B, H, KH, S, D):
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, H, D)).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, KH, S, D)).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, KH, S, D)).astype(jnp.bfloat16)
    lens = jax.random.randint(ks[3], (B,), 1, S + 1)
    got = decode_attention(q, k, v, lens, block_kv=64, interpret=True)
    want = ref.decode_attention_ref(q, k, v, lens)
    np.testing.assert_allclose(got.astype(np.float32),
                               want.astype(np.float32), rtol=5e-2, atol=5e-2)


def test_decode_attention_masks_beyond_length():
    """Garbage past cur_len must not leak into the output."""
    B, H, KH, S, D = 1, 2, 2, 128, 32
    q = jax.random.normal(KEY, (B, H, D)).astype(jnp.float32)
    k = jax.random.normal(KEY, (B, KH, S, D)).astype(jnp.float32)
    v = jax.random.normal(KEY, (B, KH, S, D)).astype(jnp.float32)
    lens = jnp.array([40], jnp.int32)
    base = decode_attention(q, k, v, lens, block_kv=32, interpret=True)
    k2 = k.at[:, :, 40:].set(1e4)
    v2 = v.at[:, :, 40:].set(-1e4)
    got = decode_attention(q, k2, v2, lens, block_kv=32, interpret=True)
    np.testing.assert_allclose(got, base, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("rows,n", [(32, 64), (64, 128), (16, 1000)])
def test_masked_softmax(rows, n):
    x = jax.random.normal(KEY, (rows, n)).astype(jnp.float32)
    m = jax.random.bernoulli(jax.random.PRNGKey(7), 0.6, (rows, n))
    m = m.at[:, 0].set(True)   # never fully-masked rows
    got = masked_softmax(x, m, block_rows=16, interpret=True)
    want = ref.masked_softmax_ref(x, m)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # masked entries exactly zero; rows sum to 1
    assert float(jnp.max(jnp.abs(jnp.where(m, 0.0, got)))) == 0.0
    np.testing.assert_allclose(jnp.sum(got, -1), 1.0, rtol=1e-5)


@pytest.mark.parametrize("rows,d", [(32, 256), (64, 512), (128, 1024)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_layernorm(rows, d, dtype):
    ks = jax.random.split(KEY, 3)
    x = (jax.random.normal(ks[0], (rows, d)) * 3 + 1).astype(dtype)
    s = jax.random.normal(ks[1], (d,)).astype(dtype)
    b = jax.random.normal(ks[2], (d,)).astype(dtype)
    got = layernorm(x, s, b, block_rows=16, interpret=True)
    want = ref.layernorm_ref(x, s, b)
    np.testing.assert_allclose(got.astype(np.float32),
                               want.astype(np.float32), **_tol(dtype))


@pytest.mark.parametrize("BH,T,K,chunk", [
    (2, 64, 32, 16), (1, 128, 64, 64), (4, 32, 16, 32),
])
def test_rwkv_chunk(BH, T, K, chunk):
    ks = jax.random.split(KEY, 5)
    r = (jax.random.normal(ks[0], (BH, T, K)) * 0.5).astype(jnp.float32)
    k = (jax.random.normal(ks[1], (BH, T, K)) * 0.5).astype(jnp.float32)
    v = (jax.random.normal(ks[2], (BH, T, K)) * 0.5).astype(jnp.float32)
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (BH, T, K))) * 0.5 + 0.45
    u = jax.random.normal(ks[4], (BH, K)) * 0.1
    got_y, got_s = rwkv_chunk(r, k, v, w, u, chunk=chunk, interpret=True)
    for b in range(BH):
        want_y, want_s = ref.rwkv_chunk_ref(
            r[b], k[b], v[b], w[b], u[b], jnp.zeros((K, K), jnp.float32))
        np.testing.assert_allclose(got_y[b], want_y, rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(got_s[b], want_s, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("B,H,KH,S,D,causal", [
    (2, 4, 2, 64, 32, True), (1, 4, 4, 128, 32, False),
])
def test_flash_custom_vjp_gradients(B, H, KH, S, D, causal):
    """§Perf iteration E: the flash backward (custom VJP) must match
    autodiff through the dense reference to f32 precision."""
    from repro.models.attention import flash_attention_fused
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, H, S, D)).astype(jnp.float32)
    k = jax.random.normal(ks[1], (B, KH, S, D)).astype(jnp.float32)
    v = jax.random.normal(ks[2], (B, KH, S, D)).astype(jnp.float32)
    do = jax.random.normal(ks[3], (B, H, S, D)).astype(jnp.float32)

    g1 = jax.grad(lambda *a: jnp.sum(
        flash_attention_fused(*a, causal, 32, 32) * do),
        argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda *a: jnp.sum(
        ref.flash_attention_ref(*a, causal=causal) * do),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("B,T,d,n,dt,c", [
    (2, 32, 64, 8, 32, 16), (1, 64, 128, 16, 64, 32), (2, 16, 32, 4, 16, 8),
])
def test_mamba_chunk(B, T, d, n, dt, c):
    from repro.kernels.mamba_chunk import mamba_chunk
    ks = jax.random.split(KEY, 3)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (B, T, d, n))) * 0.5 + 0.45
    u = (jax.random.normal(ks[1], (B, T, d, n)) * 0.3).astype(jnp.float32)
    C = (jax.random.normal(ks[2], (B, T, n)) * 0.5).astype(jnp.float32)
    y, h = mamba_chunk(a, u, C, d_tile=dt, chunk=c, interpret=True)
    for b in range(B):
        wy, wh = ref.mamba_chunk_ref(a[b], u[b], C[b])
        np.testing.assert_allclose(y[b], wy, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(h[b], wh, rtol=1e-4, atol=1e-4)


def test_ops_auto_mode_refuses_off_tpu():
    """impl=None means the compiled kernel: off a TPU it raises instead of
    quietly running the XLA reference under the kernel's name."""
    from repro.kernels import ops
    x = jnp.ones((8, 128), jnp.float32)
    with pytest.raises(ValueError, match="needs a TPU"):
        ops.layernorm(x, jnp.ones(128), jnp.zeros(128))
