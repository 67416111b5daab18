"""Operations and bytes of the work, from shapes alone, and the peaks.

These are the yardstick's own counts: what the work needs, whatever
implements it.  Roofline shares and model FLOP/s utilisation divide them
by measured times.  What a forward runs depends on the model's kind, so
the counts of a whole forward come from the kind's module.
"""
from __future__ import annotations

import json
import pathlib

from harness import kind_module

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; an unknown kind is an
    error, never a default."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: {sorted(table)}")
    return table[device_kind]


def aggregation_work(n: int, nnz: int, f: int) -> tuple[float, float]:
    """(flops, bytes) of one aggregation ``A_hat Z`` at width ``f``: a
    multiply and an add per entry and column; each entry's value and
    column index, a row pointer per row, Z read once and the output
    written once, all four bytes wide."""
    flops = 2.0 * nnz * f
    nbytes = nnz * 8.0 + (n + 1) * 4.0 + 2.0 * n * f * 4.0
    return flops, nbytes


def least_time(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The least seconds the chip could take, and which bound binds."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")


def model_flops(model: dict, n: int, nnz: int) -> float:
    """Operations of one forward over a graph, as the model's kind counts
    them (``kinds/<kind>.py``)."""
    return kind_module(model).model_flops(model, n, nnz)


def aggregation_least_time(model: dict, n: int, nnz: int, peak: dict) -> tuple[float, str]:
    """Least time of one forward's aggregations (the kind's
    ``aggregations``); the bound named is that of the last."""
    total, bound = 0.0, "bytes"
    for _, flops, nbytes in kind_module(model).aggregations(model, n, nnz):
        t, bound = least_time(flops, nbytes, peak)
        total += t
    return total, bound
