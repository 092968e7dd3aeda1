"""DeviceSlabCache: device-resident per-bucket slab operands
(DESIGN.md §13).

Every filter backend gathers a bucket's rows out of the resident
``FilterSlab`` and — for the jax / pallas / distributed paths — uploads
the gathered operands to the device on *every* ``bounds`` call, even
though the bucket → row-set mapping is fixed for the life of the slab.
On a 5k-graph DB the dense F_D upload alone dwarfs the filter math.

This cache keys on the bucket identity (the gathered row indices plus the
pad size) and holds, per bucket, the host-side gathered sub-slab and the
backend-specific device-resident operands, so each is built/transferred
once per (bucket, layout) and reused across batches.  Entries are
LRU-bounded; query-side operands (small, per-batch) are never cached.

Ownership: one cache per ``BatchedFilterEval``, created with its slab and
dropped with it.  ``invalidate()`` empties the cache — called when the
evaluator's slab is rebuilt (``BatchedFilterEval.rebuild_slab``) or when
``FlatMSQIndex.set_filter_eval`` replaces a registered evaluator, so a
stale device copy can never outlive the slab it mirrors.

Every lookup also counts in the ambient registry (``repro.obs``), under
the ``engine.`` namespace the serving stats export:
``slab_cache.<field>.hits`` / ``.misses``, ``slab_cache.evictions`` and
``slab_cache.upload_bytes`` (every field but the host gather ``sub``).
A miss's build is a ``slab_gather`` (``sub``) or ``slab_upload`` span
with the field, its rows and bytes.
"""
from __future__ import annotations

import dataclasses
import threading
from collections import OrderedDict
from typing import Any, Callable, Dict, Hashable, List, Tuple

import numpy as np

from repro.obs import ambient_count, ambient_span, current_obs


def _payload(value) -> Tuple[int, int]:
    """(leading rows, bytes) of the arrays a cached field holds: an array,
    a tuple of them, or a gathered sub-slab dataclass."""
    if hasattr(value, "nbytes") and hasattr(value, "shape"):
        return (int(value.shape[0]) if value.ndim else 1), int(value.nbytes)
    if dataclasses.is_dataclass(value):
        parts = [getattr(value, f.name) for f in dataclasses.fields(value)]
    elif isinstance(value, (tuple, list)):
        parts = value
    else:
        return 0, 0
    rows = nbytes = 0
    for p in parts:
        r, b = _payload(p)
        rows = rows or r
        nbytes += b
    return rows, nbytes


def bucket_key(idx: np.ndarray, n_pad: int) -> Tuple:
    """Cache key for one gathered bucket: exact row identity + pad size.

    The raw index bytes (not a lossy hash) — a key collision would swap
    another bucket's slab in silently, and bit-identical candidates are
    the repo's load-bearing invariant.
    """
    idx = np.ascontiguousarray(np.asarray(idx, np.int64))
    return (int(n_pad), len(idx), idx.tobytes())


class DeviceSlabCache:
    """LRU cache of per-bucket gathered sub-slabs and their device
    operands, shared by every backend path of one ``BatchedFilterEval``.
    """

    def __init__(self, max_entries: int = 16):
        self.max_entries = int(max_entries)
        self._lock = threading.Lock()
        self._entries: "OrderedDict[Hashable, Dict[str, Any]]" = \
            OrderedDict()                       # guarded_by: self._lock
        self.stats: Dict[str, int] = {          # guarded_by: self._lock
            "hits": 0, "misses": 0, "evictions": 0, "invalidations": 0}
        # duck-typed fault injector (serve.faults.FaultInjector); builds
        # fire the ``device.cache`` point so upload/gather failures are
        # injectable without a real device (DESIGN.md §18)
        self._faults = None

    def set_faults(self, faults) -> None:
        self._faults = faults

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def snapshot(self) -> Dict[str, int]:
        """Consistent copy of the counters plus the current size —
        readers must not iterate ``stats`` while a builder commits."""
        with self._lock:
            out = dict(self.stats)
            out["entries"] = len(self._entries)
            return out

    def items(self) -> List[Tuple[Hashable, str, Any]]:
        """``(key, field, value)`` for every cached field — a copy taken
        under the lock, for inspecting what is resident and where."""
        with self._lock:
            return [(k, f, v) for k, entry in self._entries.items()
                    for f, v in entry.items()]

    def get_or_build(self, key: Hashable, field: str,
                     build: Callable[[], Any]) -> Any:
        """Return the cached ``field`` of the ``key`` bucket, building it
        on first use.  Distinct fields of one bucket (host gather, jax
        arrays, pallas operands, ...) share the entry and its LRU slot."""
        with self._lock:
            entry = self._entries.get(key)
            hit = entry is not None and field in entry
            if hit:
                self._entries.move_to_end(key)
                self.stats["hits"] += 1
                value = entry[field]
        if hit:
            ambient_count(f"engine.slab_cache.{field}.hits")
            return value
        # build outside the lock: gathers/uploads are slow and re-entrant
        # callers (a field builder using another field) must not deadlock
        if self._faults is not None:
            self._faults.fire("device.cache", field=field)
        gather = field == "sub"
        obs = current_obs()
        with ambient_span("slab_gather" if gather else "slab_upload",
                          field=field) as args:
            value = build()
            if not gather and obs is not None and obs.spans.enabled:
                # a device copy is queued asynchronously: end the span
                # when the bytes are on the device, not when it is queued
                import jax
                jax.block_until_ready(value)
            rows, nbytes = _payload(value)
            args.update(rows=rows, bytes=nbytes)
        evicted = 0
        with self._lock:
            entry = self._entries.setdefault(key, {})
            self._entries.move_to_end(key)
            # first writer wins so concurrent builders agree on the object
            value = entry.setdefault(field, value)
            self.stats["misses"] += 1
            while len(self._entries) > self.max_entries:
                self._entries.popitem(last=False)
                self.stats["evictions"] += 1
                evicted += 1
        ambient_count(f"engine.slab_cache.{field}.misses")
        if evicted:
            ambient_count("engine.slab_cache.evictions", evicted)
        if not gather:
            ambient_count("engine.slab_cache.upload_bytes", nbytes)
        return value

    def invalidate(self) -> None:
        """Drop every entry (slab rebuilt / evaluator replaced)."""
        with self._lock:
            self._entries.clear()
            self.stats["invalidations"] += 1
