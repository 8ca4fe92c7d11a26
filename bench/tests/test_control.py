"""The lower-precision control fails the comparison that the program
passes: the plain reference in float32 in the program's place, at smoke
size, on three seeds."""
from __future__ import annotations

import pytest

from conftest import SMOKE_CELLS, make_smoke_root


@pytest.mark.parametrize("workload", [c[0] for c in SMOKE_CELLS])
def test_float32_control_fails(tmp_path, workload):
    from bench import control

    root = make_smoke_root(tmp_path)
    for seed in (1, 2, 2147483659):
        res = control.control(str(root), workload, seed)
        assert res["fails"], res
        assert any(c["value"] > 3 * c["limit"]
                   for c in res["checks"].values()), res
