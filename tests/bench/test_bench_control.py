"""The control — the reference one precision lower (three bfloat16
passes a matmul) in the program's place — fails the cell's check, and
the program passes it, at a size the CPU holds.  The same study at the
cells' own sizes on the chip is ``python bench/control.py``."""
import json

import pytest

import control
from bench_kit import ARCHS, tiny_cell


@pytest.mark.parametrize("model_type", ARCHS)
def test_control_fails_where_the_program_passes(tmp_path, model_type):
    found = tiny_cell(tmp_path, model_type)
    c = json.loads(found["config_file"].read_text())
    limit = c["correct"]["kv_err_max"]
    (r,) = control.study(found, [2**33 + 1], 3.0, emit=lambda s: None)
    assert r["checked_kv_rows"] >= 16
    assert r["kv_err_max"] <= limit < r["control_kv_err_max"]
    assert r["served_gap_max"] <= c["correct"]["served_gap_max"]
    # the harness's own comparison passes the program, fails the control
    assert r["correct"] is True
    assert r["control_correct"] is False
