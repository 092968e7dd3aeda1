"""DeviceSlabCache: device-resident slab operands (DESIGN.md §13).

Two kinds of entry share one cache:

* **Pinned** — the jax backend's filter keeps the evaluator's *whole*
  ``FilterSlab`` on the device, uploaded once under one fixed key
  (``("resident", layout)``: ``jax_db``, plus ``jax_packed`` for the
  packed layout), and gathers each bucket's rows on the device inside
  its jitted pass.  A pinned entry is never evicted, does not count
  against ``max_entries``, and is dropped only by ``invalidate()``.
* **LRU** — per-bucket entries keyed on the bucket identity (the
  gathered row indices plus the pad size): the host-side gathered
  sub-slab (``sub``) and the device operands built from it.  They serve
  the assignment LB (``sub``, ``lb_db``), the pallas and distributed
  filter paths, and the hot layout's host tail correction; each is built
  once per (bucket, field) and reused across batches, LRU-bounded.
  Query-side operands (small, per-batch) are never cached.

Ownership: one cache per ``BatchedFilterEval``, created with its slab and
dropped with it.  ``invalidate()`` empties the cache, pinned entries
included — called when the evaluator's slab is rebuilt
(``BatchedFilterEval.rebuild_slab``) or when
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
    """Pinned whole-slab device copies plus an LRU cache of per-bucket
    gathered sub-slabs and their device operands, shared by every backend
    path of one ``BatchedFilterEval``.
    """

    def __init__(self, max_entries: int = 16):
        self.max_entries = int(max_entries)
        self._lock = threading.Lock()
        self._entries: "OrderedDict[Hashable, Dict[str, Any]]" = \
            OrderedDict()                       # guarded_by: self._lock
        self._pinned: Dict[Hashable, Dict[str, Any]] = \
            {}                                  # guarded_by: self._lock
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
            return len(self._entries) + len(self._pinned)

    def snapshot(self) -> Dict[str, int]:
        """Consistent copy of the counters plus the current size —
        readers must not iterate ``stats`` while a builder commits."""
        with self._lock:
            out = dict(self.stats)
            out["entries"] = len(self._entries) + len(self._pinned)
            return out

    def items(self) -> List[Tuple[Hashable, str, Any]]:
        """``(key, field, value)`` for every cached field — a copy taken
        under the lock, for inspecting what is resident and where."""
        with self._lock:
            return [(k, f, v)
                    for table in (self._pinned, self._entries)
                    for k, entry in table.items() for f, v in entry.items()]

    def get_or_build(self, key: Hashable, field: str,
                     build: Callable[[], Any], *, pin: bool = False) -> Any:
        """Return the cached ``field`` of the ``key`` entry, building it
        on first use.  Distinct fields of one bucket (host gather, jax
        arrays, pallas operands, ...) share the entry and its LRU slot;
        ``pin`` keeps the entry out of the LRU until ``invalidate()``."""
        with self._lock:
            table = self._pinned if pin else self._entries
            entry = table.get(key)
            hit = entry is not None and field in entry
            if hit:
                if not pin:
                    self._entries.move_to_end(key)
                self.stats["hits"] += 1
                value = entry[field]
            generation = self.stats["invalidations"]
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
            self.stats["misses"] += 1
            # a build that raced an invalidation serves its caller but is
            # not kept: it may mirror the slab that was just replaced
            if self.stats["invalidations"] == generation:
                table = self._pinned if pin else self._entries
                entry = table.setdefault(key, {})
                if not pin:
                    self._entries.move_to_end(key)
                # first writer wins: concurrent builders agree on it
                value = entry.setdefault(field, value)
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
        """Drop every entry, pinned ones included (slab rebuilt /
        evaluator replaced)."""
        with self._lock:
            self._entries.clear()
            self._pinned.clear()
            self.stats["invalidations"] += 1
