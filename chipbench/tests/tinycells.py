"""Tiny configurations and traffic for the CPU rehearsals of the harness.

The kernel runs in interpret mode; the arithmetic is float32 throughout,
since XLA's CPU dot is exact float32, so the configurations state that.
"""
from __future__ import annotations

import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
CHIPBENCH = HERE.parent
for p in (str(CHIPBENCH), str(CHIPBENCH.parent / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402

BACKEND = "pallas_interpret"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}

MOLECULES = {
    "mean_atoms": 12.0, "sd_atoms": 5.0, "min_atoms": 2, "max_atoms": 40,
    "mean_bonds": 13.0, "max_degree": 4, "chain_prob": 0.6,
    "atom_type_probs": [0.7, 0.2, 0.1],
}


def config(n_layers: int = 2, molecules: bool = False, combination: str = "float32",
           limit: float = 1e-4) -> dict:
    cfg = {
        "name": "tiny",
        "model": {"kind": "gcn", "d_in": 8, "d_hidden": 16, "n_classes": 4,
                  "n_layers": n_layers, "backend": BACKEND},
        "precision": {"combination": combination, "aggregation": "float32"},
        "engine": {},
        "reference": "reference",
        "limits": {"out_gap": limit},
    }
    if molecules:
        cfg["molecules"] = MOLECULES
    return cfg


def full_graph_traffic(nodes: int = 200, edges: int = 800) -> dict:
    return {
        "loop": {"kind": "closed", "concurrency": 1, "warmup_requests": 1},
        "source": {"kind": "table_i", "name": "tiny", "nodes": nodes, "edges": edges,
                   "scale": 1.0, "feature_sets": 2},
    }


def stream_traffic() -> dict:
    return {
        "loop": {"kind": "closed", "concurrency": 4, "warmup_requests": 4, "warmup_seconds": 0.0},
        "source": {"kind": "molecules", "library": 5000, "max_rate_hz": 200.0},
    }


class CpuDevice:
    """Stands in for the chip in a rehearsal: the harness reads the kind,
    the platform and the memory statistics of the devices it is given."""

    platform = "cpu"
    device_kind = "cpu"

    def memory_stats(self):
        return None


def execute(cfg: dict, traffic: dict, seconds: float, seed: int = 3, trace: bool = False) -> dict:
    cell = {"name": "tiny.cell", "config": cfg["name"], "traffic": "tiny", "chips": 1}
    specs = [{"name": n, "unit": u} for n, u in
             (("setup_s", "s"), ("fullgraph_ms", "ms"), ("graphs_per_s", "graphs/s"))]
    return harness.execute(cell, cfg, traffic, specs, seed=seed, seconds=seconds, trace=trace,
                           devices=[CpuDevice()], peak=PEAK, t_start=time.monotonic(),
                           backend=BACKEND)
