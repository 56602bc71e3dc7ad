"""Share, in percent, of the Hermes kernel's least possible time in its
measured time.  The least time is the larger of its operations over the
chip's peak rate and its bytes over the peak memory bandwidth
(``chipbench/peaks.json``).  Per launch, over the ``R`` replications on
a chip and ``W`` workers:

* bytes: ``R * (3W + 1) * 4`` -- the int32 active counts in and out, the
  warm column, and the choice;
* operations: ``R * W * 16`` -- per worker lane, the slot, core and warm
  masks, the class and both scores, the mode select, the max, the index
  match and min, and the active-count update.

The bytes bound it: at the cell's sizes the operations' time is three
orders of magnitude below the bytes' time.
"""
from chipbench.metrics.hermes_select_us import read as kernel_us

OPS_PER_LANE = 16


def least_time_s(reps: int, workers: int, peaks: dict) -> tuple:
    """``(seconds, bound)`` of one launch at the chip's peaks."""
    t_ops = reps * workers * OPS_PER_LANE / peaks["flops_per_s"]
    t_bytes = reps * (3 * workers + 1) * 4 / peaks["hbm_bytes_per_s"]
    return max(t_ops, t_bytes), ("bytes" if t_bytes >= t_ops else "ops")


def read(run):
    us = kernel_us(run)
    if us is None:
        return None
    reps = run.n_reps // run.chips
    least, _ = least_time_s(reps, run.cell.config["deployment"]["n_workers"],
                            run.peaks)
    return least / (us * 1e-6) * 100.0
