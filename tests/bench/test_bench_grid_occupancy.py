"""The grid-occupancy reader: the share of the expert kernels' static
grid that active blocks cover, over the traced steps."""
import pytest

import run


def step(padded, grid=None, useful=0):
    s = {"rows": 14, "emitted": 10, "sched/padded_rows": float(padded),
         "sched/useful_rows": float(useful)}
    if grid is not None:
        s["sched/grid_rows"] = float(grid)
    return s


def test_occupancy_sums_over_the_traced_steps():
    read = run.reader("dispatch.grid_occupancy.serve")
    steps = [step(416, 1152, 168), step(480, 1152, 192), step(96, 128, 12)]
    assert read({"steps": steps}) == pytest.approx(100.0 * 992 / 2432)


def test_occupancy_is_none_where_the_program_counts_no_grid_rows():
    read = run.reader("dispatch.grid_occupancy.serve")
    assert read({"steps": [step(416), step(480)]}) is None
    assert read({"steps": [step(416, 1152), step(480)]}) is None
    assert read({"steps": []}) is None
    assert read({}) is None
