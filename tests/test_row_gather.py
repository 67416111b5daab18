"""Row-gather kernel body for ultra-sparse capacity segments (DESIGN.md §2).

On integer-valued inputs every sum is exact in f32, so the row-gather
body (``body="rows"``), the vector body and the segment-sum oracle must
agree bit for bit, whatever the order of accumulation.  The cases cover
what the row-gather body does differently: coverage and inert padding
tiles, full tiles, many entries on one Z row, block-rows split across
grid-step groups and across launches, the accumulate and zero-seeded
chains, and feature widths that are not a multiple of 128 (one lane tile
of Z fetches single rows; wider Z fetches 8-row slabs).
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import coo_from_dense, coo_to_scv_tiles
from repro.core.scv import (
    DEFAULT_LADDER,
    DEFAULT_TILE,
    plan_from_tiles,
    plan_from_tiles_bucketed,
    uses_row_gather,
)
from repro.kernels.scv_spmm import ops as kops
from repro.kernels.scv_spmm import ref as kref
from repro.kernels.scv_spmm.scv_spmm import resolve_body


def _int_matrix(rng, m, n, density):
    return ((rng.random((m, n)) < density) * rng.integers(1, 5, (m, n))).astype(
        np.float32
    )


def _with_inert_tail(plan, n):
    """Append ``n`` zero-nnz tiles repeating the last tile's coordinates, as
    the serving assembler pads a composite's tile count."""
    def tail(a, fill=0):
        pad = np.full((n,) + a.shape[1:], fill, a.dtype)
        return jnp.asarray(np.concatenate([np.asarray(a), pad]))

    tr, tc = np.asarray(plan.tile_row), np.asarray(plan.tile_col)
    return dataclasses.replace(
        plan,
        tile_row=tail(tr, tr[-1]), tile_col=tail(tc, tc[-1]),
        rows=tail(plan.rows), cols=tail(plan.cols), vals=tail(plan.vals),
        nnz_in_tile=tail(plan.nnz_in_tile), perm=None,
    )


def _case(name, rng):
    """(matrix, plan, feature width, kwargs for scv_spmm_plan)."""
    if name == "padding":
        # block-rows 3.. hold no entry: coverage dummies; then inert tiles
        a = np.zeros((160, 160), np.float32)
        a[:48] = _int_matrix(rng, 48, 160, 0.05)
        plan = plan_from_tiles(
            coo_to_scv_tiles(coo_from_dense(a), 16, cap=8), with_perm=False
        )
        return a, _with_inert_tail(plan, 37), 24, {}
    if name == "nnz_eq_cap":
        a = np.zeros((64, 64), np.float32)
        a[np.arange(8), np.arange(8) * 2] = 3  # one tile with 8 entries
        a[16:24, 40] = rng.integers(1, 5, 8)  # a full tile on one Z row
        a[33, 50] = 2
        plan = plan_from_tiles(
            coo_to_scv_tiles(coo_from_dense(a), 16, cap=8), with_perm=False
        )
        assert int(np.asarray(plan.nnz_in_tile).max()) == 8
        return a, plan, 8, {}
    if name == "shared_z_row":
        a = _int_matrix(rng, 128, 128, 0.02)
        a[:, 7] = rng.integers(1, 5, 128)  # every row reads Z row 7
        plan = plan_from_tiles_bucketed(
            coo_to_scv_tiles(coo_from_dense(a), 32, cap=64), caps=(8, 16, 64)
        )
        return a, plan, 16, {}
    if name == "split_groups":
        # 150 tiles a block-row at cap 8: each block-row spans three groups
        # of 64 tiles, and with 100 tiles a launch, two launches
        a = _int_matrix(rng, 40, 2400, 0.004)
        a[:, ::16] = rng.integers(1, 5, (40, 150))
        plan = plan_from_tiles(
            coo_to_scv_tiles(coo_from_dense(a), 16, cap=8), with_perm=False
        )
        return a, plan, 12, {"max_launch_tiles": 100}
    if name == "cap32_groups":
        # cap 32 at T = 64: groups of 16 tiles, four to an SMEM block
        a = _int_matrix(rng, 192, 1600, 0.05)
        plan = plan_from_tiles(
            coo_to_scv_tiles(coo_from_dense(a), 64, cap=32), with_perm=False
        )
        return a, plan, 40, {}
    if name == "init_zeros":
        a = _int_matrix(rng, 128, 128, 0.05)
        plan = plan_from_tiles_bucketed(
            coo_to_scv_tiles(coo_from_dense(a), 32, cap=64), caps=(8, 64),
            ensure_coverage=False,
        )
        return a, plan, 16, {"init": "zeros"}
    if name == "wide_slab":
        # Z wider than one lane tile: 8-row slabs, two feature blocks
        a = _int_matrix(rng, 96, 96, 0.05)
        plan = plan_from_tiles_bucketed(
            coo_to_scv_tiles(coo_from_dense(a), 32, cap=64), caps=(8, 64)
        )
        return a, plan, 200, {"feature_block": 128}
    raise KeyError(name)


CASES = ["padding", "nnz_eq_cap", "shared_z_row", "split_groups",
         "cap32_groups", "init_zeros", "wide_slab"]


@pytest.mark.parametrize("case", CASES)
def test_rows_equals_vector_and_oracle(case, rng, monkeypatch):
    a, plan, f, kw = _case(case, rng)
    if "max_launch_tiles" in kw:
        monkeypatch.setattr(kops, "MAX_LAUNCH_TILES", kw.pop("max_launch_tiles"))
        assert plan.n_tiles > kops.MAX_LAUNCH_TILES
    z = jnp.asarray(rng.integers(-4, 5, (a.shape[1], f)).astype(np.float32))
    oracle = np.asarray(kref.scv_spmm_reference_plan(plan, z))
    np.testing.assert_array_equal(oracle[: a.shape[0]], a @ np.asarray(z))
    for body in ("rows", "vector"):
        out = np.asarray(
            kops.scv_spmm_plan(plan, z, interpret=True, body=body, **kw)
        )
        np.testing.assert_array_equal(out, oracle, err_msg=body)


@pytest.mark.parametrize(
    "cap,tile,n_tiles,body",
    [(8, 64, 65536, "rows"), (32, 64, 8192, "rows"), (128, 64, 65536, "vector"),
     (8, 64, 64, "vector"), (16, 32, 2048, "rows"), (32, 32, 4096, "vector")],
)
def test_auto_body_follows_capacity_and_size(cap, tile, n_tiles, body):
    assert uses_row_gather(cap, tile, n_tiles) == (body == "rows")
    assert resolve_body("auto", cap, tile, n_tiles) == body
    assert resolve_body("vector", cap, tile, n_tiles) == "vector"
    assert resolve_body("rows", cap, tile, n_tiles) == "rows"
    with pytest.raises(ValueError, match="unknown kernel body"):
        resolve_body("per_entry", cap, tile, n_tiles)


def test_row_gather_launches_count_small_caps(rng):
    """Of the arxiv-like ladder (8, 32, 128) at T = 64, the cap-8 segment's
    launches count as row-gather launches, and not cap 128's; nor cap 32's,
    whose segment here is too small for the row-gather body."""
    import jax

    from repro.core.scv import ROW_GATHER_MIN_TILES
    from repro.models.gnn import GNNConfig, build_graph, init_gnn
    from repro.serve.graph_engine import (
        GraphEngineConfig,
        GraphRequest,
        GraphServeEngine,
        plan_launches,
    )
    from repro.simul.datasets import gcn_normalize, powerlaw_graph

    assert DEFAULT_LADDER == (8, 32, 128) and DEFAULT_TILE == 64
    n = 6000
    adj = gcn_normalize(powerlaw_graph(n, 24000, seed=4))
    plan = build_graph(adj, tile=64, bucket_caps=DEFAULT_LADDER).plan
    segs = plan.segments
    # the composite pads each segment's tile count to a power of two
    assert segs[0].n_tiles >= ROW_GATHER_MIN_TILES >= 2 * segs[1].n_tiles
    assert segs[2].n_tiles > 0  # the diagonal tiles reach cap 128
    assert plan_launches(plan, row_gather=True) == 1

    cfg = GNNConfig(name="gcn", kind="gcn", d_in=8, d_hidden=8, n_classes=4)
    params, _ = init_gnn(jax.random.PRNGKey(0), cfg)
    eng = GraphServeEngine({"gcn": (params, cfg)}, GraphEngineConfig())
    x = rng.standard_normal((n, 8)).astype(np.float32)
    eng.submit(GraphRequest(rid=0, adj=adj, x=x, model="gcn"))
    eng.run()
    m = eng.metrics()
    # the composite's three segments, one launch each a layer
    assert m["launches"] == 3 * cfg.n_layers
    assert m["row_gather_launches"] == cfg.n_layers


@pytest.fixture
def row_gather_everywhere(monkeypatch):
    """Let segments of any size take the row-gather body, so that small
    graphs drive it through the model, the engine and the executor."""
    import repro.core.scv as scv

    monkeypatch.setattr(scv, "ROW_GATHER_MIN_TILES", 0)


@pytest.mark.parametrize("kind", ["gcn", "sage", "gin", "gat"])
def test_forward_and_grads_through_row_gather(kind, rng, row_gather_everywhere):
    """gnn_forward_jit on the row-gather body (cap-8 and cap-16 segments at
    T = 32) against the jnp backend, forward and parameter gradients."""
    import jax

    from repro.models.gnn import GNNConfig, build_graph, gnn_forward_jit, init_gnn
    from repro.simul.datasets import gcn_normalize, powerlaw_graph

    adj = gcn_normalize(powerlaw_graph(160, 1200, seed=3))
    g = build_graph(adj, tile=32, bucket_caps=(8, 16, 64))
    assert sum(uses_row_gather(s.cap, s.tile, s.n_tiles) for s in g.plan.segments) == 2
    x = jnp.asarray(rng.standard_normal((160, 12)).astype(np.float32))

    def run(backend):
        cfg = GNNConfig(
            name=f"rg-{kind}", kind=kind, d_in=12, d_hidden=16,
            n_classes=4, n_layers=2, backend=backend,
        )
        params, _ = init_gnn(jax.random.PRNGKey(0), cfg)

        def loss(p):
            y = gnn_forward_jit(p, cfg, g, x)
            return jnp.sum(y * y)

        return loss(params), jax.grad(loss)(params)

    y_k, g_k = run("pallas_interpret")
    y_r, g_r = run("jnp")
    np.testing.assert_allclose(float(y_k), float(y_r), rtol=2e-4)
    for a, b in zip(jax.tree_util.tree_leaves(g_k), jax.tree_util.tree_leaves(g_r)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-3)


def test_engine_composites_through_row_gather(rng, row_gather_everywhere):
    """The serving engine's composites (coverage tail, inert tile padding)
    on the row-gather body match each graph's own jnp forward."""
    import jax

    from repro.models.gnn import GNNConfig, build_graph, gnn_forward, init_gnn
    from repro.serve.graph_engine import GraphEngineConfig, GraphRequest, GraphServeEngine
    from repro.simul.datasets import gcn_normalize, powerlaw_graph

    adjs = [gcn_normalize(powerlaw_graph(n, 4 * n, seed=20 + n)) for n in (70, 130, 50)]
    cfg = GNNConfig(name="gcn", kind="gcn", d_in=8, d_hidden=8, n_classes=4,
                    backend="pallas_interpret")
    params, _ = init_gnn(jax.random.PRNGKey(0), cfg)
    eng = GraphServeEngine({"gcn": (params, cfg)}, GraphEngineConfig(tile=64))
    xs = [rng.standard_normal((a.shape[0], 8)).astype(np.float32) for a in adjs]
    for i, (a, x) in enumerate(zip(adjs, xs)):
        eng.submit(GraphRequest(rid=i, adj=a, x=x, model="gcn"))
    done = eng.run()
    assert len(done) == 3 and eng.metrics()["row_gather_launches"] > 0
    ref_cfg = dataclasses.replace(cfg, backend="jnp")
    for r in done:
        ref = gnn_forward(params, ref_cfg, build_graph(r.adj, tile=64), jnp.asarray(r.x))
        np.testing.assert_allclose(r.out, np.asarray(ref), atol=1e-5, rtol=1e-5)


def test_sharded_spans_through_row_gather():
    """The 8-fake-device executor parity (tiles, features and 2-D meshes,
    zero-seeded chains over spans) with the row-gather body on every span."""
    import json
    import subprocess
    import sys

    from test_acc_chain import CHAIN_SHARD_SCRIPT

    patch = "import repro.core.scv as _scv\n_scv.ROW_GATHER_MIN_TILES = 0\n"
    script = CHAIN_SHARD_SCRIPT.replace("res = {}\n", patch + "res = {}\n", 1)
    assert patch in script
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=600, cwd=str(__import__("pathlib").Path(__file__).parents[1]),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert all(v for k, v in res.items() if k.startswith(("bit_", "valid_"))), res
