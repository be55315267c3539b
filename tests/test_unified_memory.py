"""Fig. 4/5 address mapping + §3.2 capacity accounting properties."""
import hypothesis
from hypothesis import given, settings, strategies as st

from repro.configs import paper_models as pm
from repro.core import AddressMap, WeightTiler, partitioned_plan, \
    shared_fraction, unified_plan

AMAP = AddressMap()
TILER = WeightTiler(AMAP)


@given(row=st.integers(0, AMAP.n_rows - 1),
       ch=st.integers(0, AMAP.n_channels - 1),
       bank=st.integers(0, AMAP.n_banks - 1),
       col=st.integers(0, AMAP.row_bytes - 1))
@settings(max_examples=200, deadline=None)
def test_address_encode_decode_bijective(row, ch, bank, col):
    addr = AMAP.encode(row, ch, bank, col)
    assert AMAP.decode(addr) == (row, ch, bank, col)
    assert 0 <= addr < AMAP.capacity_bytes


@given(st.integers(0, AMAP.capacity_bytes - 1))
@settings(max_examples=200, deadline=None)
def test_address_decode_encode_bijective(addr):
    assert AMAP.encode(*AMAP.decode(addr)) == addr


@given(w_rows=st.integers(1, 4096), w_cols=st.integers(1, 4096))
@settings(max_examples=40, deadline=None)
def test_tile_no_row_conflicts(w_rows, w_cols):
    """All weight rows of one tile land on the SAME DRAM row address across
    DISTINCT (channel, bank) pairs — the Fig. 4 zero-row-conflict property
    that lets all banks/channels MAC in parallel."""
    import random
    rnd = random.Random(0)
    tile_r = min(TILER.tile.rows, w_rows)
    c = rnd.randrange(min(TILER.tile.cols, w_cols))
    seen = set()
    rows = set()
    for r in range(tile_r):
        row, ch, bank, col = AMAP.decode(
            TILER.element_address(w_rows, w_cols, r, c))
        rows.add(row)
        assert (ch, bank) not in seen
        seen.add((ch, bank))
    assert len(rows) == 1              # single row activation per tile


@given(w_rows=st.integers(1, 8192), w_cols=st.integers(1, 8192))
@settings(max_examples=60, deadline=None)
def test_distinct_elements_distinct_addresses(w_rows, w_cols):
    import random
    rnd = random.Random(1)
    pts = {(rnd.randrange(w_rows), rnd.randrange(w_cols))
           for _ in range(32)}
    addrs = {TILER.element_address(w_rows, w_cols, r, c) for r, c in pts}
    assert len(addrs) == len(pts)


def test_row_activation_count_misalignment():
    """GPT-2 L (d=1280) needs 2x the activations of M (d=1024) per output
    row group — the paper's §6.2 energy explanation."""
    acts_m = TILER.rows_activated(1024, 1024)
    acts_l = TILER.rows_activated(1024, 1280)
    assert acts_l == 2 * acts_m


def test_shared_fraction_gpt2_about_91_percent():
    fr = shared_fraction(pm.GPT2_XL)
    assert 0.85 <= fr <= 0.97      # paper: ~91% for GPT-2


def test_unified_vs_partitioned_capacity():
    cap = 8 << 30
    for cfg in (pm.GPT2_M, pm.GPT2_L, pm.GPT2_XL):
        u = unified_plan(cfg, cap)
        p = partitioned_plan(cfg, cap)
        assert u.fits
        assert u.duplicated_bytes == 0
        # partitioned duplicates the shared FC params -> ~2x footprint
        assert p.footprint > 1.7 * u.footprint * shared_fraction(cfg)
        assert p.pim_throughput_factor == 0.5
        assert u.pim_throughput_factor == 1.0


def test_partitioned_2p5b_cannot_duplicate():
    """GPT-2 2.5B: 5 GB of weights on a 2x4 GB partition — the shared params
    no longer fit twice; transfers appear (paper Fig. 13 discussion)."""
    p = partitioned_plan(pm.GPT2_2p5B, 8 << 30)
    assert p.transfer_bytes_per_step > 0


def test_tpu_unified_layout():
    """The TPU realization: one NamedSharding serves prefill and decode."""
    import jax
    from repro.core.unified_memory import assert_unified_layout
    from repro.models import transformer as T
    from repro.configs import get_arch
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    stats = assert_unified_layout(
        T.param_defs(get_arch("llama3.2-1b").reduced()), mesh)
    assert stats["resharded_bytes"] == 0
