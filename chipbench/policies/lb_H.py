"""Hermes hybrid balancer (arXiv:2111.07226 Sec. 4.2), plain numpy.

Low load (some worker has a free core): among workers with a free core
prefer class 3 = busy with a warm executor for the function, 2 = busy,
1 = idle with a warm executor, 0 = idle; within a class the most loaded
(packing).  High load: the least loaded worker with a free slot, a warm
executor breaking ties.  The first best worker wins; -1 when every
worker is full.
"""
import numpy as np

_BIG = 1 << 40


def select(active: np.ndarray, warm_col: np.ndarray, cores: int,
           slots: int) -> int:
    has_slot = active < slots
    if not has_slot.any():
        return -1
    has_core = active < cores
    warm = (warm_col > 0).astype(np.int64)
    if has_core.any():
        cls = np.where(active > 0, 2 + warm, warm)
        score = np.where(has_core, cls * (slots + 1) + active, -_BIG)
    else:
        score = np.where(has_slot, -(2 * active - warm), -_BIG)
    return int(np.argmax(score))
