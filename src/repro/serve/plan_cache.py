"""Plan cache: amortize SCV preprocessing across repeated graph queries.

The paper builds SCV host-side ("statically generated from the COO format",
§III-C) — a per-graph cost the serving path would otherwise repay on every
request.  This module caches the prepared plan (the ``Graph`` bundle from
``models/gnn.py``: SCV tiles + permutation, in numpy for the engine's
member plans and on the device for its composites) keyed by a content
hash of the COO adjacency, so hot graphs skip preprocessing entirely.

Design:

* **Content-hash keys** — ``coo_content_key`` hashes the raw (rows, cols,
  vals, shape) bytes plus the plan parameters (tile, cap), so two requests
  carrying the same adjacency — even built independently — share one plan,
  and plans built under different tilings never collide.  Composite
  (batched) plans derive their key from the member digests via
  ``combine_keys``: the *composed* arrays are never re-hashed (member
  adjacencies are still hashed once per wave to identify them).

* **LRU + byte budget** — entries are evicted least-recently-used when
  either the entry-count or the byte budget is exceeded.  Bytes are
  accounted from the device/host arrays actually held by the plan.

* **TTL / refresh** — with ``max_age_s`` set, an entry older than the TTL
  is treated as a miss on lookup (dropped and counted in
  ``stats.expired``), so ``get_or_build`` transparently rebuilds it — the
  refresh policy for serving processes whose graph contents drift under a
  stable content key is "expire and rebuild on next touch".  The clock is
  injected (defaults to ``time.monotonic``) so policies are testable
  without sleeping.

* **Counters** — hits / misses / evictions / expirations / bytes for the
  serving metrics endpoint and the benchmark's hit-rate report.

The cache is deliberately value-agnostic: ``get_or_build`` takes a builder
callback, so the engine caches single-graph plans and composite batch
plans (and, later, partitioned multi-device plans) through one code path.
"""
from __future__ import annotations

import dataclasses
import hashlib
import threading
import time
from collections import OrderedDict
from typing import Any, Callable, Iterable, Optional

import numpy as np

from repro.core.formats import COOMatrix


# ---------------------------------------------------------------------------
# content keys
# ---------------------------------------------------------------------------
def coo_content_key(adj: COOMatrix, *, tile: int, cap: Any = None) -> str:
    """Stable content hash of a COO adjacency + plan parameters.

    ``cap`` is the capacity signature: an int for single-cap plans, the
    ascending bucket ladder tuple for nnz-bucketed plans (the layout is
    plan aux, so it must key the cached device object)."""
    h = hashlib.blake2b(digest_size=16)
    h.update(f"shape={adj.shape};tile={tile};cap={cap};".encode())
    for a in (adj.rows, adj.cols, adj.vals):
        arr = np.ascontiguousarray(a)
        # frame each array with dtype + length: raw bytes alone would let
        # byte-aliased arrays of different dtypes/lengths collide
        h.update(f"{arr.dtype.str}:{arr.shape[0]};".encode())
        h.update(arr.tobytes())
    return h.hexdigest()


def combine_keys(keys: Iterable[str], *, salt: str = "") -> str:
    """Key for a composite plan derived from already-keyed members.

    Hashing the member digests (plus a salt carrying batch parameters such
    as the padding bucket) is orders of magnitude cheaper than re-hashing
    the composed arrays, and equal batches — same members, same order,
    same bucket — collapse to one plan.
    """
    h = hashlib.blake2b(digest_size=16)
    h.update(salt.encode())
    for k in keys:
        h.update(k.encode())
    return h.hexdigest()


def delta_key(key: str, delta: Any) -> str:
    """Key of a cached plan after applying ``delta`` (a
    ``stream.DeltaBatch``): hash of the old key + the delta's framed byte
    signature.  Chaining digests is orders of magnitude cheaper than
    re-hashing a mutated million-edge adjacency, at the cost that the
    chained key differs from ``coo_content_key`` of the final adjacency
    computed cold — a graph is either tracked by deltas or keyed by
    content, never both (see serve/README.md).
    """
    h = hashlib.blake2b(digest_size=16)
    h.update(b"delta;")
    h.update(key.encode())
    h.update(delta.signature())
    return h.hexdigest()


def plan_nbytes(plan: Any) -> int:
    """Best-effort byte footprint of a cached plan.

    Walks the object for numpy / jax arrays (dataclass fields, dicts,
    tuples/lists) and sums ``nbytes``.  Shared arrays are counted once
    (identity-deduped).
    """
    seen: set[int] = set()
    total = 0

    def visit(obj):
        nonlocal total
        if obj is None or isinstance(obj, (int, float, str, bool, bytes)):
            return
        oid = id(obj)
        if oid in seen:
            return
        seen.add(oid)
        nb = getattr(obj, "nbytes", None)
        if nb is not None and isinstance(nb, (int, np.integer)):
            total += int(nb)
            return
        if isinstance(obj, dict):
            for v in obj.values():
                visit(v)
        elif isinstance(obj, (list, tuple)):
            for v in obj:
                visit(v)
        elif dataclasses.is_dataclass(obj):
            for f in dataclasses.fields(obj):
                visit(getattr(obj, f.name))

    visit(plan)
    return total


# ---------------------------------------------------------------------------
# the cache
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class PlanCacheStats:
    hits: int = 0
    misses: int = 0
    evictions: int = 0
    expired: int = 0  # TTL drops (also counted as misses on lookup)
    revalidated: int = 0  # delta-patched entries re-keyed in place
    anchored: int = 0  # delta-chained keys re-homed to content keys
    bytes_in_use: int = 0
    entries: int = 0
    build_seconds: float = 0.0

    @property
    def hit_rate(self) -> float:
        tot = self.hits + self.misses
        return self.hits / tot if tot else 0.0


@dataclasses.dataclass
class _Entry:
    value: Any
    nbytes: int
    created: float = 0.0  # clock() at insertion (TTL anchor)


class PlanCache:
    """Content-addressed LRU cache of prepared aggregation plans.

    ``max_age_s`` (optional) bounds entry staleness: lookups drop entries
    older than the TTL and report a miss, so hot keys are rebuilt in place.
    ``clock`` is injectable for tests (monotonic seconds).

    Thread safety: every public method takes one reentrant lock, so
    concurrent lookups, revalidations, and anchors never observe a
    half-applied mutation (the async scheduler loop builds/revalidates
    while other threads read metrics or probe keys).  The lock is held
    across ``get_or_build``'s builder call — reentrancy is what lets a
    composite build nest its member builds — which serializes builders;
    that is the engine's single-consumer discipline anyway (only the
    scheduler thread builds plans).
    """

    def __init__(
        self,
        max_entries: int = 256,
        max_bytes: int = 512 * 1024 * 1024,
        max_age_s: Optional[float] = None,
        clock: Callable[[], float] = time.monotonic,
    ):
        if max_entries <= 0:
            raise ValueError("max_entries must be positive")
        if max_bytes <= 0:
            raise ValueError("max_bytes must be positive")
        if max_age_s is not None and max_age_s <= 0:
            raise ValueError("max_age_s must be positive (or None to disable)")
        self.max_entries = max_entries
        self.max_bytes = max_bytes
        self.max_age_s = max_age_s
        self._clock = clock
        self._entries: OrderedDict[str, _Entry] = OrderedDict()
        self.stats = PlanCacheStats()
        self._build_depth = 0  # nested get_or_build (composite -> members)
        self._lock = threading.RLock()

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def __contains__(self, key: str) -> bool:
        with self._lock:
            return self._live_entry(key) is not None

    @property
    def keys(self) -> list[str]:
        """Keys in LRU order (least-recently-used first)."""
        with self._lock:
            return list(self._entries)

    def _live_entry(self, key: str) -> Optional[_Entry]:
        """Entry for ``key`` if present and within TTL; expired entries are
        dropped (counted in ``stats.expired``) and reported absent."""
        e = self._entries.get(key)
        if e is None:
            return None
        if self.max_age_s is not None and self._clock() - e.created > self.max_age_s:
            self._entries.pop(key)
            self.stats.bytes_in_use -= e.nbytes
            self.stats.expired += 1
            self.stats.entries = len(self._entries)
            return None
        return e

    def get(self, key: str) -> Optional[Any]:
        """Look up a plan; counts a hit/miss and refreshes recency.
        An entry past ``max_age_s`` counts as a miss (and is dropped)."""
        with self._lock:
            e = self._live_entry(key)
            if e is None:
                self.stats.misses += 1
                return None
            self._entries.move_to_end(key)
            self.stats.hits += 1
            return e.value

    def peek(self, key: str) -> Optional[Any]:
        """Look up without touching recency or hit/miss counters
        (introspection); still drops entries past the TTL."""
        with self._lock:
            e = self._live_entry(key)
            return e.value if e is not None else None

    def put(self, key: str, value: Any, nbytes: Optional[int] = None) -> None:
        if nbytes is None:
            nbytes = plan_nbytes(value)
        with self._lock:
            old = self._entries.pop(key, None)
            if old is not None:
                self.stats.bytes_in_use -= old.nbytes
            if nbytes > self.max_bytes:
                # an entry that can never fit would evict the whole cache on
                # its way in and then be evicted itself — skip it instead
                self.stats.entries = len(self._entries)
                return
            self._entries[key] = _Entry(value, int(nbytes), created=self._clock())
            self.stats.bytes_in_use += int(nbytes)
            self._evict()
            self.stats.entries = len(self._entries)

    def get_or_build(
        self,
        key: str,
        builder: Callable[[], Any],
        nbytes: Optional[int] = None,
    ) -> Any:
        """Return the cached plan for ``key``, building (and caching) it on
        a miss.  Oversized plans (> max_bytes on their own) are still
        returned but not retained."""
        with self._lock:
            value = self.get(key)
            if value is not None:
                return value
            # build_seconds accumulates only at the outermost nesting level:
            # a composite builder calls get_or_build for its members, and
            # the outer elapsed time already contains theirs
            self._build_depth += 1
            t0 = time.perf_counter()
            try:
                value = builder()
            finally:
                dt = time.perf_counter() - t0
                self._build_depth -= 1
                if self._build_depth == 0:
                    self.stats.build_seconds += dt
            nb = plan_nbytes(value) if nbytes is None else int(nbytes)
            if nb <= self.max_bytes:
                self.put(key, value, nb)
            return value

    def revalidate(
        self,
        key: str,
        delta: Any,
        patch: Optional[Callable[[Any], Any]] = None,
    ) -> str:
        """Re-key the entry at ``key`` for a delta-mutated graph instead of
        letting the mutation become a full miss.

        Returns ``delta_key(key, delta)`` — the key the patched plan lives
        under.  If the entry is live and ``patch`` is given, the cached
        value is patched (``patch(value)``, typically
        ``stream.apply_delta``), stored under the new key, and counted in
        ``stats.revalidated``; the old key is dropped.  If the entry is
        absent (evicted/expired) the new key is still returned so the
        caller's next ``get_or_build`` rebuilds from the mutated source —
        revalidation degrades to a plain miss, never to a stale hit.
        """
        new_key = delta_key(key, delta)
        with self._lock:
            e = self._live_entry(key)
            if e is None or patch is None:
                return new_key
            self._entries.pop(key)
            self.stats.bytes_in_use -= e.nbytes
            self.stats.entries = len(self._entries)
            self.put(new_key, patch(e.value))
            self.stats.revalidated += 1
            return new_key

    def anchor(self, key: str, content_key: str) -> str:
        """Re-home a live entry from a delta-chained key to the content
        key of its *current* adjacency.

        ``revalidate`` chains digests (``delta_key``), so a long-lived
        tracked graph drifts away from ``coo_content_key`` of its actual
        adjacency — an untracked client submitting the identical graph
        would miss and build a duplicate entry.  Periodically re-homing
        the entry under the content key re-joins the two key spaces and
        bounds the drift window.  Counted in ``stats.anchored``; if the
        entry is dead (evicted/expired) the content key is still returned
        so the caller re-keys and the next build lands content-addressed.
        """
        with self._lock:
            e = self._live_entry(key)
            if e is None or content_key == key:
                return content_key
            self._entries.pop(key)
            self.stats.bytes_in_use -= e.nbytes
            self.put(content_key, e.value, e.nbytes)
            self.stats.anchored += 1
            return content_key

    def _evict(self) -> None:
        while self._entries and (
            len(self._entries) > self.max_entries
            or self.stats.bytes_in_use > self.max_bytes
        ):
            _, e = self._entries.popitem(last=False)
            self.stats.bytes_in_use -= e.nbytes
            self.stats.evictions += 1

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self.stats.bytes_in_use = 0
            self.stats.entries = 0
