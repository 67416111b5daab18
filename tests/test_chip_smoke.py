"""chip_smoke.py's phases, rehearsed on the CPU at a tiny size.

The script itself runs only on a TPU; here its phases run with the
kernel in interpret mode (``backend="pallas_interpret"``), including the
comparison with the float64 reference and the failed-request check.
"""
import importlib.util
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core.formats import COOMatrix
from repro.models.gnn import init_gnn
from repro.serve.graph_engine import GraphRequest
from repro.simul.datasets import gcn_normalize, powerlaw_graph

ROOT = pathlib.Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"

_spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
cs = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(cs)


def _cpu_env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    env.update(extra)
    return env


def test_one_chip_phases_on_cpu():
    cfg = cs.gcn_paper_config("pallas_interpret")
    big = gcn_normalize(powerlaw_graph(300, 900, seed=0))
    mols = cs.molecule_graphs(2 * cs.WAVE_GRAPHS)
    r = cs.run_one_chip(cfg, big, mols)
    assert r["kernel_rel_err"] <= cs.AGG_RTOL
    assert r["gcn_rel_err"] <= cs.GCN_RTOL
    assert r["completed"] == 3 + 2 * len(mols) + cs.WAVE_GRAPHS
    assert r["launches"] > 0


def test_compare_catches_a_dropped_edge():
    """The end-to-end tolerance is far below what one lost edge costs."""
    cfg = cs.gcn_paper_config("pallas_interpret")
    params, _ = init_gnn(jax.random.PRNGKey(0), cfg)
    adj = cs.molecule_graphs(1)[0]
    x = np.random.default_rng(0).standard_normal((adj.shape[0], cfg.d_in))
    ref = cs.reference_gcn(params, adj, x)
    assert cs.compare("self", ref, ref, cs.GCN_RTOL) == 0.0
    keep = np.arange(adj.nnz) != 0
    dropped = COOMatrix(adj.rows[keep], adj.cols[keep], adj.vals[keep], adj.shape)
    with pytest.raises(cs.SmokeFailure, match="error"):
        cs.compare("dropped edge", cs.reference_gcn(params, dropped, x), ref,
                   cs.GCN_RTOL)
    with pytest.raises(cs.SmokeFailure, match="non-finite"):
        cs.compare("nan", np.full_like(ref, np.nan), ref, cs.GCN_RTOL)


def test_failed_request_is_caught():
    """The async loop isolates and ejects a failing wave instead of
    raising; the smoke test must still fail on it."""
    cfg = cs.gcn_paper_config("pallas_interpret")
    params, _ = init_gnn(jax.random.PRNGKey(0), cfg)
    params["layer1"]["w"] = params["layer1"]["w"][:7]  # wrong fan-in
    mols = cs.molecule_graphs(2)
    engine = cs.build_engine(cfg, params, 64)
    reqs = [
        GraphRequest(rid=i, adj=a, model=cfg.name,
                     x=np.zeros((a.shape[0], cfg.d_in), np.float32))
        for i, a in enumerate(mols)
    ]
    with pytest.raises(cs.SmokeFailure, match="failed"):
        cs.serve_async(engine, reqs, timeout_s=120)
    assert engine.metrics()["failed"] == len(reqs)


def test_entry_point_refuses_cpu(tmp_path):
    """No TPU: exit non-zero and print no result line, from the repo and
    from a directory that holds the script alone."""
    shutil.copy(SCRIPT, tmp_path / SCRIPT.name)
    alone = _cpu_env()
    del alone["PYTHONPATH"]
    for script, env in ((SCRIPT, _cpu_env()), (tmp_path / SCRIPT.name, alone)):
        out = subprocess.run(
            [sys.executable, str(script)], env=env, cwd=script.parent,
            capture_output=True, text=True, timeout=300,
        )
        assert out.returncode != 0, out
        assert '"ok": true' not in out.stdout


def test_four_chip_phase_on_a_cpu_mesh():
    """``--chips 4``'s phase on four virtual CPU devices."""
    code = (
        "import jax, chip_smoke as cs\n"
        "from repro.simul.datasets import gcn_normalize, powerlaw_graph\n"
        "big = gcn_normalize(powerlaw_graph(1500, 9000, seed=0))\n"
        "r = cs.run_four_chips(cs.gcn_paper_config('pallas_interpret'), big,"
        " jax.devices()[:4])\n"
        "assert set(r) == {'tiles:t4f1', 'features:t1f4', '2d:t2f2',"
        " 'engine_sharded_gcn_rel_err'}, r\n"
        "assert r['tiles:t4f1']['plan_bytes_per_device']"
        " < r['tiles:t4f1']['plan_bytes_one_device']\n"
        "print('FOUR_OK')\n"
    )
    env = _cpu_env(XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, cwd=ROOT,
        capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "FOUR_OK" in out.stdout


def test_compile_cache_dir(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins; otherwise one fixed in-repo path."""
    from jax.experimental.compilation_cache import compilation_cache

    from repro.launch.graph_serve import DEFAULT_COMPILE_CACHE, enable_compile_cache

    assert DEFAULT_COMPILE_CACHE == ROOT / ".jax_cache"
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == prev
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache() == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(ROOT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
        compilation_cache.reset_cache()
