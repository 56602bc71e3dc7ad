"""Processor sharing on a worker, plain numpy: each of its ``n`` active
invocations runs at ``min(1, cores / n)`` cores."""
import numpy as np


def rates(n_active: np.ndarray, cores: int, dtype) -> np.ndarray:
    """Per-worker rate of every active invocation (0 on idle workers)."""
    n = n_active.astype(dtype)
    with np.errstate(divide="ignore"):
        r = np.minimum(dtype(1.0), dtype(cores) / n)
    return np.where(n_active > 0, r, dtype(0.0)).astype(dtype)
