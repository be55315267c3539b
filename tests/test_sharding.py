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
