"""The control, the plain reference computed one precision step below
the configuration's bfloat16 (float8 e4m3 matmul operands) and put in
the program's place, fails the cells' own limits; at the same cut
size the program passes them. The chip readings at each cell's own
size are in PERF.md; ``bench/calibrate.py`` takes them."""
import pytest

from bench import calibrate
from bench_tiny import WIDE, tiny_cell

SEEDS = [2 ** 31 + 21, 2 ** 31 + 22, 2 ** 31 + 23]


def _rows(cell, fn, *args):
    rows = []
    fn(cell, *args, rows.append, require_tpu=False)
    return [r for r in rows if "seed" in r]


def _fails(reading, limits):
    return any(reading[k] > limits[k] for k in limits)


def test_train_control_fails_and_program_passes():
    cell = tiny_cell("smollm-135m.train")
    for r in _rows(cell, calibrate.train_readings, SEEDS):
        assert not _fails(r["program"], cell.limits), r
        assert _fails(r["control"], cell.limits), r
        assert _fails(r["half_batch"], cell.limits), r


def test_serve_control_fails_and_program_passes():
    cell = tiny_cell("granite-3-2b.serve", sizes=WIDE)
    for r in _rows(cell, calibrate.serve_readings, SEEDS, 2.0, []):
        assert not _fails(r["program"], cell.limits), r
        assert _fails(r["control"], cell.limits), r
