"""The comparisons that decide `correct`, and the sampling they use."""

from __future__ import annotations

import numpy as np


def max_rel(a, b) -> float:
    """Largest |a - b| / |b| over all elements (b the reference); inf
    where `a` is not finite or the shapes differ."""
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    if a.shape != b.shape or not np.isfinite(a).all():
        return float("inf")
    return float((np.abs(a - b) / np.maximum(np.abs(b), 1e-30)).max())


def max_rel_of(got: dict, ref: dict, keys) -> float:
    """The widest `max_rel` over the named arrays."""
    return max(max_rel(got[k], ref[k]) for k in keys)


def sample(seed: int, n: int, k: int, salt: int) -> np.ndarray:
    """k distinct indices of range(n), drawn from the run's seed (the
    salt keeps the sample apart from the traffic's own draws)."""
    rng = np.random.default_rng([salt, seed % (1 << 63)])
    return np.sort(rng.choice(n, size=min(k, n), replace=False))


def digest(arrays: dict) -> bytes:
    """Bytes of every output array, to tell calls apart."""
    import hashlib
    h = hashlib.blake2b(digest_size=16)
    for k in sorted(arrays):
        a = np.ascontiguousarray(np.asarray(arrays[k]))
        h.update(k.encode())
        h.update(str((a.dtype, a.shape)).encode())
        h.update(a.tobytes())
    return h.digest()
