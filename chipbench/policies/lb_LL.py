"""Least-loaded balancer (join the shortest queue), plain numpy.

The worker with the fewest active invocations among those with a free
slot; the first such worker wins; -1 when every worker is full.
"""
import numpy as np

_BIG = 1 << 40


def select(active: np.ndarray, warm_col: np.ndarray, cores: int,
           slots: int) -> int:
    has_slot = active < slots
    if not has_slot.any():
        return -1
    return int(np.argmin(np.where(has_slot, active, _BIG)))
