"""Shared device-resident feature buffers (``BufferRegistry``).

Every ``Trainer`` on the device-resident fused path needs its pipeline's
column store (``arrays={"x": feats, "y": labs}``) placed on device.  Without
sharing, N concurrent train/tune requests against the same dataset pay N
``device_put`` transfers and hold N copies of an O(n·d) feature matrix in
device memory.  The registry deduplicates them: a column is placed once and
every consumer receives the SAME device buffer object (buffers are never
donated — ``train.engine`` donates only the train state — so sharing is
safe).

Keying is two-tier, per column:

  * **identity fast path** — ``id(array)`` (guarded by a weakref so a
    recycled id can never alias a dead array) maps straight to the placed
    buffer; repeat requests with the same host array never rehash it.
  * **content fingerprint** — otherwise the column is hashed
    (sha256 of bytes + shape + dtype, the same scheme as the artifact
    store's data fingerprint), so two *equal* arrays owned by different
    clients still share one device buffer.

``put_count`` counts actual device placements and ``hits`` counts reuses —
the observable behind the "N Trainers, one buffer" test and bench claims.
"""
from __future__ import annotations

import hashlib
import threading
import weakref

import jax.numpy as jnp
import numpy as np


def array_fingerprint(arr: np.ndarray) -> str:
    """Content identity of one host column (dtype/shape-qualified)."""
    a = np.ascontiguousarray(arr)
    h = hashlib.sha256()
    h.update(str(a.dtype).encode())
    h.update(str(a.shape).encode())
    h.update(a)  # the buffer in place: the digest of a.tobytes(), uncopied
    return h.hexdigest()[:16]


class BufferRegistry:
    """Device-resident column cache keyed on array identity/fingerprint."""

    def __init__(self):
        self._lock = threading.RLock()
        self._buffers: dict[str, jnp.ndarray] = {}          # fingerprint -> device buffer
        self._id_cache: dict[int, tuple[weakref.ref, str]] = {}  # id -> (ref, fp)
        self.put_count = 0
        self.hits = 0

    # -- fingerprinting -----------------------------------------------------

    def fingerprint(self, arr: np.ndarray) -> str:
        """``array_fingerprint`` with an identity memo: the same host array
        object is hashed once, however many requests carry it."""
        arr = np.asarray(arr)
        with self._lock:
            cached = self._id_cache.get(id(arr))
            if cached is not None:
                ref, fp = cached
                if ref() is arr:
                    return fp
                del self._id_cache[id(arr)]  # id was recycled
        fp = array_fingerprint(arr)
        with self._lock:
            try:
                self._id_cache[id(arr)] = (weakref.ref(arr), fp)
            except TypeError:  # pragma: no cover — non-weakref-able view
                pass
        return fp

    # -- placement ----------------------------------------------------------

    def column(self, arr: np.ndarray) -> jnp.ndarray:
        """The shared device buffer for one host column (placed on first
        request, reused afterwards)."""
        fp = self.fingerprint(arr)
        with self._lock:
            buf = self._buffers.get(fp)
            if buf is not None:
                self.hits += 1
                return buf
        placed = jnp.asarray(arr)
        with self._lock:
            # lost a race: keep the first placement so identity stays stable
            buf = self._buffers.get(fp)
            if buf is not None:
                self.hits += 1
                return buf
            self._buffers[fp] = placed
            self.put_count += 1
            return placed

    def get(self, arrays: dict[str, np.ndarray]) -> dict[str, jnp.ndarray]:
        """Shared device buffers for a pipeline column store."""
        return {k: self.column(v) for k, v in arrays.items()}

    # -- lifecycle ----------------------------------------------------------

    def release(self, arr_or_fp) -> bool:
        """Drop one column (by host array or fingerprint) from the registry.
        Existing consumers keep their references; only future sharing stops."""
        fp = arr_or_fp if isinstance(arr_or_fp, str) else self.fingerprint(arr_or_fp)
        with self._lock:
            return self._buffers.pop(fp, None) is not None

    def clear(self) -> None:
        with self._lock:
            self._buffers.clear()
            self._id_cache.clear()

    def stats(self) -> dict[str, int]:
        with self._lock:
            return {
                "resident_columns": len(self._buffers),
                "put_count": self.put_count,
                "hits": self.hits,
            }
