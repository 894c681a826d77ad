"""`jax.random.split` of a raw threefry key, computed on the host.

The serving engine draws one key a request at admission. Splitting the
engine's key with `jax.random.split` runs a program on the DEVICE and
the fetch of its half waits for everything queued before it, the
admission's own prefill included (DEVIATIONS §9). Threefry-2x32 is
twenty rounds of 32-bit adds, rotates and xors, so numpy on two words
gives the same bits with no round trip, and the sequence of keys a seed
and an order of admissions produce stays what it was.
`tests/test_serving_host_prng.py` holds it to `jax.random.split` under
both settings of `jax_threefry_partitionable`.
"""

from typing import Tuple

import jax
import numpy as np

_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))
_PARITY = np.uint32(0x1BD11BDA)


def _threefry2x32(k1, k2, x0, x1):
    """jax's `threefry2x32_p` on uint32 numpy ARRAYS (numpy warns of
    overflow on scalars, and the hash lives on wrap-around)."""
    ks = (k1, k2, k1 ^ k2 ^ _PARITY)
    x0 = x0 + ks[0]
    x1 = x1 + ks[1]
    for i in range(5):
        for r in _ROTATIONS[i % 2]:
            x0 = x0 + x1
            x1 = (x1 << np.uint32(r)) | (x1 >> np.uint32(32 - r))
            x1 = x0 ^ x1
        x0 = x0 + ks[(i + 1) % 3]
        x1 = x1 + ks[(i + 2) % 3] + np.uint32(i + 1)
    return x0, x1


def split(key: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """The two uint32[2] rows of `jax.random.split(key)` for a raw
    threefry `key` that is already on the host."""
    k1, k2 = np.asarray(key, np.uint32).reshape(2, 1)
    lo = np.arange(2, dtype=np.uint32)
    if jax.config.jax_threefry_partitionable:
        # one 64-bit counter a new key, its high word 0
        rows = np.stack(_threefry2x32(k1, k2, np.zeros_like(lo), lo), 1)
    else:
        # counters 0..3 hashed as the pairs (0, 2) and (1, 3)
        rows = np.concatenate(
            _threefry2x32(k1, k2, lo, lo + np.uint32(2))
        ).reshape(2, 2)
    return rows[0], rows[1]
