"""Autotuner: simulator-scored search over plan configurations.

It scores every candidate ``(T, C, dense-threshold-ratio, ladder)``
analytically — :func:`repro.tune.cost.predict_cost` (``run_scv_bucketed``
cycles + slot-priced traffic + per-launch overhead) plus two kernel-body
terms the plan-level model cannot see (chunk-step overhead/padding and the
MXU/VPU crossover of the dense-tile split).  One simulator run per
distinct tile is shared across every ladder at that tile, so the sweep is
O(tiles) simulator passes, not O(candidates).  The best-scored candidate
becomes the :class:`TunedConfig` (``source="simulated"``), cached in a
:class:`TuneStore` keyed by quantized histogram signature x machine
fingerprint (see ``signature.py`` for the staleness rule).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import numpy as np

from repro.core.formats import COOMatrix
from repro.core.scv import (
    MXU_VPU_RATIO,
    bucket_caps_for,
    tile_nnz_histogram,
)
from repro.simul.dataflows import run_scv_bucketed
from repro.simul.machine import MachineConfig

from repro.tune.config import TunedConfig
from repro.tune.cost import CLOCK_HZ, CostEstimate, predict_cost
from repro.tune.signature import cache_key, histogram_signature, machine_fingerprint
from repro.tune.store import TuneStore

#: Fixed per-chunk-step cost of the vectorized kernel body, in
#: entry-equivalents (grid bookkeeping + scatter/gather setup per step).
CHUNK_STEP_ENTRIES = 64

#: Candidate tiles.  Powers of two around the lane width; T > 256 makes
#: T^2 dense fallback blocks exceed VMEM budgets, T < 16 defeats the MXU.
TILE_CANDIDATES = (32, 64, 128)
CHUNK_CANDIDATES = (64, 128, 256)
RATIO_CANDIDATES = (MXU_VPU_RATIO / 2, MXU_VPU_RATIO, MXU_VPU_RATIO * 2)


@dataclasses.dataclass(frozen=True)
class ScoredCandidate:
    config: TunedConfig
    estimate: CostEstimate
    score_s: float  # estimate.seconds + chunk + dense terms

    def to_json(self) -> dict:
        return {
            "config": self.config.to_json(),
            "predicted_s": self.score_s,
            "estimate": self.estimate.to_json(),
        }


@dataclasses.dataclass
class TuneResult:
    key: str
    config: TunedConfig
    cached: bool
    candidates: list = dataclasses.field(default_factory=list)
    search_seconds: float = 0.0


def candidate_ladders(counts: np.ndarray, tile: int) -> tuple[tuple[int, ...], ...]:
    """Contiguous sub-ladders of the derived full ladder for ``tile``.

    ``bucket_caps_for`` gives the max-depth ladder; shallower contiguous
    slices trade dummy/padding slots against launch count.  Chain-splitting at ``caps[-1]`` makes
    every slice valid regardless of the heaviest tile.
    """
    full = bucket_caps_for(counts, tile)
    out = []
    for i in range(len(full)):
        for j in range(i + 1, len(full) + 1):
            out.append(full[i:j])
    return tuple(dict.fromkeys(out))


class Autotuner:
    """Search + cache driver.  Thread a shared :class:`TuneStore` through
    several tuners (or processes) to share the on-disk cache."""

    def __init__(
        self,
        machine: Optional[MachineConfig] = None,
        store: Optional[TuneStore] = None,
        *,
        tiles: tuple[int, ...] = TILE_CANDIDATES,
        chunks: tuple[int, ...] = CHUNK_CANDIDATES,
        ratios: tuple[float, ...] = RATIO_CANDIDATES,
    ):
        self.machine = machine if machine is not None else MachineConfig()
        self.store = store if store is not None else TuneStore()
        self.tiles = tuple(tiles)
        self.chunks = tuple(chunks)
        self.ratios = tuple(ratios)
        self.searches = 0
        self.cache_hits = 0
        self.last_result: Optional[TuneResult] = None

    # -- public entry ------------------------------------------------------
    def tune(self, adj: COOMatrix, n_features: int = 64) -> TunedConfig:
        """Resolve the config for ``adj``: cache hit, or a search."""
        if adj.nnz == 0:
            return TunedConfig.default()
        counts_ref = tile_nnz_histogram(adj, TunedConfig.default().tile)
        key = cache_key(
            histogram_signature(counts_ref), machine_fingerprint(self.machine)
        )
        hit = self.store.get(key)
        if hit is not None:
            self.cache_hits += 1
            self.last_result = TuneResult(key=key, config=hit, cached=True)
            return hit
        t0 = time.perf_counter()
        self.searches += 1
        scored = self._score(adj, n_features)
        result = TuneResult(
            key=key,
            config=dataclasses.replace(scored[0].config, source="simulated"),
            cached=False,
            candidates=scored,
        )
        result.search_seconds = time.perf_counter() - t0
        self.store.put(
            key,
            result.config,
            meta={
                "n_candidates": len(scored),
                "search_seconds": result.search_seconds,
            },
        )
        self.last_result = result
        return result.config

    # -- analytic scoring ---------------------------------------------------
    def _score(self, adj: COOMatrix, n_features: int) -> list[ScoredCandidate]:
        scored = []
        default = TunedConfig.default()
        for tile in self.tiles:
            counts = tile_nnz_histogram(adj, tile)
            base = run_scv_bucketed(
                adj, n_features, self.machine, tile,
                caps=bucket_caps_for(counts, tile),
            )
            ladders = candidate_ladders(counts, tile)
            if tile == default.tile and default.bucket_caps not in ladders:
                ladders = ladders + (default.bucket_caps,)
            for caps in ladders:
                chunk = self._best_chunk(counts, tile, caps, n_features)
                ratio = self._best_ratio(counts, tile, n_features)
                cfg = TunedConfig(
                    tile=tile,
                    chunk=chunk,
                    dense_threshold_ratio=ratio,
                    bucket_caps=caps,
                )
                est = predict_cost(
                    adj, cfg, n_features, machine=self.machine, compute=base
                )
                score = (
                    est.seconds
                    + self._chunk_term(counts, tile, caps, chunk, n_features)
                    + self._dense_term(counts, tile, ratio, n_features)
                )
                scored.append(ScoredCandidate(cfg, est, score))
        scored.sort(key=lambda c: c.score_s)
        return scored

    def _entry_seconds(self, n_features: int) -> float:
        """One VPU entry-update in seconds: ceil(F / N_PE) cycles."""
        return -(-n_features // self.machine.n_pe) / CLOCK_HZ

    def _chunk_term(self, counts, tile, caps, chunk, n_features) -> float:
        """Chunk-step overhead + intra-chunk padding of the kernel body.

        A tile at cap ``c`` runs ``ceil(c / C)`` steps; each step costs a
        fixed ``CHUNK_STEP_ENTRIES`` bookkeeping charge and processes a
        full ``C``-wide chunk, so work is ``steps * (C +
        CHUNK_STEP_ENTRIES)`` entry-equivalents per tile.
        """
        per_cap = _segment_tile_counts(counts, caps)
        entries = 0.0
        for cap, n_tiles in per_cap.items():
            steps = -(-cap // chunk)
            entries += n_tiles * steps * (min(chunk, cap) + CHUNK_STEP_ENTRIES)
        return entries * self._entry_seconds(n_features) / self.machine.n_vpe

    def _best_chunk(self, counts, tile, caps, n_features) -> int:
        return min(
            self.chunks,
            key=lambda c: self._chunk_term(counts, tile, caps, c, n_features),
        )

    def _dense_term(self, counts, tile, ratio, n_features) -> float:
        """Signed cost delta of densifying tiles above ``T^2 * ratio``:
        a densified tile trades its nnz VPU entry-updates for a dense
        ``T^2 * MXU_VPU_RATIO`` entry-equivalent MXU matmul."""
        counts_arr = np.asarray(counts, dtype=np.int64)
        thresh = int(tile * tile * ratio)
        dense = counts_arr[counts_arr > thresh]
        if dense.size == 0:
            return 0.0
        mxu_equiv = tile * tile * MXU_VPU_RATIO
        delta_entries = float((mxu_equiv - dense).sum())
        return delta_entries * self._entry_seconds(n_features) / self.machine.n_vpe

    def _best_ratio(self, counts, tile, n_features) -> float:
        return min(
            self.ratios,
            key=lambda r: self._dense_term(counts, tile, r, n_features),
        )


def _segment_tile_counts(counts, caps) -> dict[int, int]:
    """Launched tiles per cap after chain-splitting ``counts`` at the top
    cap (no coverage dummies — callers add those where they matter)."""
    caps_arr = np.asarray(sorted(int(c) for c in caps), dtype=np.int64)
    counts_arr = np.asarray(counts, dtype=np.int64)
    counts_arr = counts_arr[counts_arr > 0]
    top = int(caps_arr[-1])
    out = {int(c): 0 for c in caps_arr}
    if counts_arr.size:
        out[top] += int((counts_arr // top).sum())
        rem = counts_arr % top
        rem = rem[rem > 0]
        if rem.size:
            idx = np.searchsorted(caps_arr, rem)
            for i, n in zip(*np.unique(idx, return_counts=True)):
                out[int(caps_arr[i])] += int(n)
    return out
