"""Seeded inputs and the percentile every metric uses.  Everything a run feeds
the program is drawn here from ``--seed``; a cell's workload file holds only
parameters (sizes), never code."""
import numpy as np


def fold_seed(seed):
    """``--seed`` may exceed 32 signed bits; jax and numpy keys take 31."""
    return int(seed) % (2**31 - 1)


def image_batch(rng, n, size, classes):
    """(images f32 [n, size, size, 3],), (labels i32 [n],): random pixels and
    labels, what ``example/image-classification --benchmark 1`` feeds."""
    x = rng.standard_normal((n, size, size, 3), dtype=np.float32)
    return (x,), (rng.integers(0, classes, (n,), dtype=np.int32),)


def percentile(values, q):
    """The q-th percentile (0-100) by linear interpolation, as
    ``numpy.percentile``; None for no samples."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))
