"""Simulator-pruned autotuner for SCV plan configuration (DESIGN.md §8).

Public surface:

* :class:`TunedConfig` — frozen (T, C, ratio, ladder) record; the only
  sanctioned carrier of tile/cap/chunk values outside ``core/scv.py``.
* :class:`Autotuner` — simulator-scored search with an on-disk
  :class:`TuneStore` cache.
* ``histogram_signature`` / ``machine_fingerprint`` / ``cache_key`` — the
  regime-keyed cache scheme.
"""
from repro.tune.autotuner import Autotuner, ScoredCandidate, TuneResult
from repro.tune.config import TunedConfig
from repro.tune.cost import (
    CostEstimate,
    plan_launched_slots,
    plan_slot_bytes,
    predict_cost,
)
from repro.tune.signature import (
    cache_key,
    histogram_signature,
    machine_fingerprint,
    quantize_histogram,
)
from repro.tune.store import TuneStore

__all__ = [
    "Autotuner",
    "CostEstimate",
    "ScoredCandidate",
    "TuneResult",
    "TuneStore",
    "TunedConfig",
    "cache_key",
    "histogram_signature",
    "machine_fingerprint",
    "plan_launched_slots",
    "plan_slot_bytes",
    "predict_cost",
    "quantize_histogram",
]
