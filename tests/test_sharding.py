"""Logical-axis sharding rules: divisibility fallback, conflict resolution,
GQA cache layouts."""
import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.sharding.axes import logical_spec


def _mesh(shape, names):
    from repro.launch.mesh import make_mesh
    return make_mesh(shape, names)


@pytest.fixture(scope="module")
def mesh():
    # 1 real device, abstract mesh via make_mesh is not possible; use a
    # 1x1 mesh for rule-resolution tests (extent>1 cases need fake devices
    # -> covered by the dry-run) — so build Mesh from a device array view.
    import numpy as np
    from jax.sharding import Mesh
    dev = np.array(jax.devices()[:1])
    return Mesh(dev.reshape(1, 1), ("data", "model"))


def test_extent1_axes_drop(mesh):
    spec = logical_spec((8, 16), ("batch", "heads"), mesh)
    assert spec == P(None, None)   # extent-1 axes never shard


class _FakeMesh:
    """Rule-resolution-only mesh stand-in (no devices needed)."""
    def __init__(self, sizes):
        self.axis_names = tuple(sizes)
        import numpy as np
        self.devices = np.empty(tuple(sizes.values()))


def test_divisibility_fallback():
    m = _FakeMesh({"data": 16, "model": 16})
    # kv_heads=8 on a 16-way model axis: replicate
    spec = logical_spec((128, 8, 32768, 64),
                        ("batch", "kv_heads", "kv_seq", "head_dim"), m)
    assert spec[1] is None
    # ... and the cache sequence dim claims 'model' instead (GQA fallback)
    assert spec[2] == "model"


def test_kv_heads_claim_model_when_divisible():
    m = _FakeMesh({"data": 16, "model": 16})
    spec = logical_spec((128, 16, 32768, 64),
                        ("batch", "kv_heads", "kv_seq", "head_dim"), m)
    assert spec[0] == "data" and spec[1] == "model"
    assert spec[2] is None            # model already claimed


def test_batch_frees_data_for_seq_when_not_divisible():
    m = _FakeMesh({"data": 16, "model": 16})
    # long_500k: batch=1 cannot use 'data'; nothing else wants it here
    spec = logical_spec((1, 8, 524288, 64),
                        ("batch", "kv_heads", "kv_seq", "head_dim"), m)
    assert spec[0] is None
    assert spec[2] == "model"


def test_multipod_batch_uses_both_axes():
    m = _FakeMesh({"pod": 2, "data": 16, "model": 16})
    spec = logical_spec((256, 4096), ("batch", "seq"), m)
    assert spec[0] == ("pod", "data")


def test_no_axis_used_twice():
    m = _FakeMesh({"data": 16, "model": 16})
    spec = logical_spec((256, 384, 7168, 2048),
                        ("batch", "experts", "fsdp", "d_ff"), m)
    used = []
    for s in spec:
        if s is None:
            continue
        used.extend(s if isinstance(s, tuple) else [s])
    assert len(used) == len(set(used))
    assert spec[1] == "model" and spec[0] == "data"
    assert spec[2] is None            # fsdp wants 'data' but batch holds it


def test_fsdp_weights_shard_both_axes():
    m = _FakeMesh({"data": 16, "model": 16})
    spec = logical_spec((384, 7168, 2048), ("experts", "fsdp", "d_ff"), m)
    assert spec[0] == "model" and spec[1] == "data"


def test_cache_kv_seq_claims_model_after_kv_heads():
    """The cache stores kv_seq BEFORE kv_heads (position-major); kv_seq
    still takes 'model' only when kv_heads could not."""
    m = _FakeMesh({"data": 16, "model": 16})
    axes = ("layers", "batch", "kv_seq", "kv_heads", "head_dim")
    spec = logical_spec((4, 128, 32768, 16, 64), axes, m)
    assert spec[2] is None and spec[3] == "model"
    spec = logical_spec((4, 128, 32768, 8, 64), axes, m)
    assert spec[2] == "model" and spec[3] is None


_MESH_DECODE = """
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_arch
from repro.launch.mesh import make_mesh
from repro.models import transformer as T
from repro.models.attention import seq_sharded
from repro.models.params import init_params

base = get_arch("llama3.2-1b").reduced()
mesh = make_mesh((1, 4), ("data", "model"))
for kh, layout_b in ((4, False), (2, True)):
    cfg = dataclasses.replace(base, num_kv_heads=kh)
    assert seq_sharded(kh, mesh) == layout_b
    params = init_params(T.param_defs(cfg), jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 6), 0,
                              cfg.vocab_size)
    outs = []
    for use_mesh in (False, True):
        step = jax.jit(lambda p, t, c, l: T.decode_step(cfg, p, t, c, l),
                       donate_argnums=2)
        cache = init_params(T.cache_defs(cfg, 2, 16), jax.random.PRNGKey(0))
        lens = jnp.zeros((2,), jnp.int32)
        got = []
        for t in range(toks.shape[1]):
            with (mesh if use_mesh else jax.sharding.Mesh(
                    np.array(jax.devices()[:1]).reshape(1, 1),
                    ("data", "model"))):
                lg, cache = step(params, toks[:, t:t + 1], cache, lens)
            lens = lens + 1
            got.append(np.asarray(lg, np.float32))
        outs.append(np.stack(got, 1))
    np.testing.assert_allclose(outs[0], outs[1], rtol=2e-2, atol=2e-2)
print("ok")
"""


def test_decode_on_a_model_mesh_matches_one_device():
    """Decode on a 4-way 'model' mesh matches one device in both cache
    layouts: A (KV heads sharded; the token's K/V row is scattered in
    place) and B (too few KV heads, so the cache is sequence-sharded and
    the token is written by the onehot select). Four host devices exist
    only in a fresh process."""
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(root, "src"))
    out = subprocess.run([sys.executable, "-c", _MESH_DECODE], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0 and out.stdout.strip().endswith("ok"), \
        out.stderr[-3000:]
