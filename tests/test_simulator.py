"""Simulator invariants + the paper's qualitative claims (§V)."""
import numpy as np
import pytest

from repro.simul import MachineConfig, geomean, load, simulate
from repro.simul.datasets import gcn_normalize, powerlaw_graph


@pytest.fixture(scope="module")
def ultra():
    return load("arxiv", max_edges=120_000)


@pytest.fixture(scope="module")
def highly():
    return load("cobuy_photo", max_edges=120_000)


def test_iso_mac_across_formats(ultra):
    """Paper §V-A: comparisons are iso-MAC (BCSR is the deliberate dense
    exception)."""
    f = 64
    macs = {
        fmt: simulate(ultra.adj, f, fmt).compute.macs
        for fmt in ["csr", "csc", "scv", "scv_z", "mp"]
    }
    ref = macs["csr"]
    for fmt, m in macs.items():
        assert m == ref, (fmt, m, ref)
    bcsr = simulate(ultra.adj, f, "bcsr", block=16).compute.macs
    assert bcsr > ref  # dense blocks do extra MACs


def test_scv_compute_beats_csr_on_ultra_sparse(ultra):
    res = {fmt: simulate(ultra.adj, 128, fmt) for fmt in ["csr", "csc", "scv_z"]}
    assert res["csr"].compute_cycles > res["scv_z"].compute_cycles
    assert res["csc"].compute_cycles >= res["scv_z"].compute_cycles


def test_idle_cycles_ordering(ultra):
    res = {fmt: simulate(ultra.adj, 128, fmt) for fmt in ["csr", "scv_z"]}
    # Fig. 8: orders of magnitude more idle for CSR on ultra-sparse
    assert res["csr"].idle_cycles > 50 * max(res["scv_z"].idle_cycles, 1)


def test_traffic_reduction(ultra, highly):
    for g in (ultra, highly):
        res = {fmt: simulate(g.adj, 128, fmt) for fmt in ["csr", "csc", "scv_z"]}
        assert res["csr"].traffic_bytes > res["scv_z"].traffic_bytes
        assert res["csc"].traffic_bytes > res["scv_z"].traffic_bytes


def test_overall_speedup_positive(ultra, highly):
    for g in (ultra, highly):
        res = {
            fmt: simulate(g.adj, 128, fmt)
            for fmt in ["csr", "csc", "mp", "scv_z"]
        }
        for base in ["csr", "csc", "mp"]:
            assert res[base].total_cycles > res["scv_z"].total_cycles, base


def test_scv_z_no_worse_than_scv(ultra):
    rz = simulate(ultra.adj, 128, "scv_z")
    rr = simulate(ultra.adj, 128, "scv")
    # Z ordering helps (or at least does not hurt) cache-level traffic
    assert rz.memory.dram_bytes <= rr.memory.dram_bytes * 1.05


def test_width_sweep_width1_wins(ultra):
    """Fig. 13: widening tiles beyond 1 column hurts (zero-skipping
    granularity)."""
    from repro.simul.dataflows import run_scv_width

    cfg = MachineConfig()
    lat = {}
    for w in [1, 4, 16]:
        comp, traffic = run_scv_width(ultra.adj, 128, cfg, height=64, width=w)
        lat[w] = traffic.total_bytes
    assert lat[1] < lat[4] < lat[16]


def test_multipass_traffic_regular_but_compute_heavy(ultra):
    mp = simulate(ultra.adj, 128, "mp")
    scv = simulate(ultra.adj, 128, "scv_z")
    assert mp.compute_cycles > scv.compute_cycles  # re-scan overhead
    assert mp.memory.mat <= scv.memory.mat * 1.5  # regular DRAM access


def test_dataset_registry_stats():
    from repro.simul.datasets import TABLE_I

    assert len(TABLE_I) == 10
    g = load("citeseer", max_edges=50_000)
    spec = TABLE_I["citeseer"]
    assert abs(g.adj.shape[0] - spec.nodes) / spec.nodes < 0.05
    # density should be in the ballpark of Table I (self loops added)
    dens = g.adj.nnz / (g.adj.shape[0] ** 2)
    assert dens < 10 * (spec.edges / spec.nodes**2 + 1.0 / spec.nodes)


def test_dataset_load_is_the_same_graph_in_every_process():
    """``load`` seeds from a stable digest of the name: processes with
    different string-hash salts draw the identical graph."""
    import hashlib
    import os
    import pathlib
    import subprocess
    import sys

    from repro.simul import datasets
    from repro.simul.datasets import name_seed

    src = str(pathlib.Path(datasets.__file__).parents[2])

    assert name_seed("arxiv") == 64760  # pinned: crc32(b"arxiv") % 2**16
    code = (
        "import hashlib; from repro.simul.datasets import load; "
        "a = load('citeseer', max_edges=20_000).adj; "
        "print(hashlib.sha256(a.rows.tobytes() + a.cols.tobytes()"
        " + a.vals.tobytes()).hexdigest())"
    )
    digests = set()
    for salt in ("0", "12345"):
        env = dict(os.environ, PYTHONHASHSEED=salt, PYTHONPATH=src)
        out = subprocess.run(
            [sys.executable, "-c", code], env=env, check=True,
            capture_output=True, text=True,
        )
        digests.add(out.stdout.strip())
    a = load("citeseer", max_edges=20_000).adj
    digests.add(hashlib.sha256(
        a.rows.tobytes() + a.cols.tobytes() + a.vals.tobytes()
    ).hexdigest())
    assert len(digests) == 1
