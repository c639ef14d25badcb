"""The control reads far above sound runs of the program, at a small size
on the CPU.

The control is the reference put in the program's place, its contractions
at ``high`` (three bf16 passes, emulated, so that the CPU computes what a
TPU would), one step below the configurations' ``highest``.  On the chip,
at each cell's own size, ``bench/calibrate.py`` takes the readings the
limits are set from (PERF.md).  Here, at a small size with dense near ties,
the number that shows the control's rounding has to read at least three
times higher for the control than for the program on every seed: the
per-subset ASSE, the reported SSE against its own centroids.  That sound small runs pass the cells' limits is
``test_faults.test_clean_small_run_is_correct``.
"""
import pytest

import calibrate
import cells

CELLS = [("sift1m-k256.ipkmeans", "ipkmeans", "subset_asse_gap_median"),
         ("sift1m-ivf4096.train", "train", "sse_gap")]
SEEDS = (2 ** 31 + 5, 2 ** 31 + 6, 2 ** 31 + 7)


@pytest.mark.parametrize("name,traffic,number", CELLS)
def test_control_reads_far_above_the_program(name, traffic, number):
    cell = cells.small(name, traffic, cells.DENSE)
    program = [calibrate.job_readings(cell, s, False) for s in SEEDS]
    control = [calibrate.job_readings(cell, s, True) for s in SEEDS]
    worst = max(r[number] for r in program)
    assert min(r[number] for r in control) >= 3.0 * worst, (
        [r[number] for r in program], [r[number] for r in control])
