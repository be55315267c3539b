"""Every cell, end to end on the CPU at rehearsal sizes: the run is
correct, reports its metrics, compiles nothing inside its window, and a
traced run reads its per-layer counters."""
import pytest

import cells
from rehearse import rehearse

CELLS = cells.names()


def _listed(cell, kind):
    """{name: source} of the ``kind`` metrics BENCHMARK.json gives ``cell``."""
    return {m["name"]: m["source"] for m in cells.load(cell).bench[kind]
            if cell in m.get("workloads", [cell])}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses(cell):
    out = rehearse(cell, seed=2**31 + 17, seconds=2.0)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == set(_listed(cell, "end_to_end"))
    assert "setup_s" in out["metrics"] and len(out["metrics"]) >= 2
    assert out["info"]["window_compiles"] == 0
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("cell", CELLS)
def test_traced_rehearsal_reads_counters(cell):
    out = rehearse(cell, seed=5, seconds=2.0, trace=True)
    assert out["correct"], out["checks"]
    assert set(out["info"]["profiler_stall_s"]) == {"start", "stop"}
    # no TPU trace on the CPU: device metrics are left out, never zero
    host = {n for n, src in _listed(cell, "per_layer").items()
            if src != "device_trace"}
    assert host and set(out["metrics"]) == host
