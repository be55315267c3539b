"""The control: the reference in fp8 (e4m3, one scale per tensor) put in
the program's place goes through the run's own check against the cell's
limit and comes out not correct, where the bf16 program comes out
correct. On the chip at the cells' sizes this sets the upper end of each
limit (``tools/control.py``); here it runs at rehearsal sizes, against
the rehearsal limit (``cells.REHEARSAL_GAP_LIMIT``)."""
import pytest

import cells
from rehearse import rehearse


@pytest.mark.parametrize("cell", cells.names())
def test_control_is_not_correct(cell):
    out = rehearse(cell, seed=23, seconds=2.0, control=True)
    assert out["correct"], out["checks"]
    control = out["info"]["control"]
    assert control["correct"] is False, control["checks"]
    assert control["checks"]["served_gap"]["value"] > \
        out["checks"]["served_gap"]["limit"]
    assert control["argmax_differs"] > out["info"]["argmax_differs"]
