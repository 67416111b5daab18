"""Hybrid MXU/VPU kernel + nnz-bucketed plans (DESIGN.md §2).

Covers the PR's acceptance criteria:

* vectorized kernel body == jnp oracle, bit for bit on
  integer-valued inputs (order-independent sums) — including the
  in-kernel dense-tile branch,
* kernel edge cases: zero-tile plans, all-dummy (coverage-only) tiles,
  the nnz == cap boundary, cap not a multiple of the chunk size,
* bucketed segments are byte-identical to slices of the scalar-loop
  (`_coo_to_scv_tiles_loop`-era) tile construction,
* jit == eager for the bucketed plan under ``interpret=True``,
* grad parity (dvals / dZ) and forward parity for all four model kinds,
  bucketed plans flowing through ``gnn_forward_jit`` and
  ``assemble_batched_graph``,
* the legacy no-``nnz_in_tile`` path masks d/dvals on structural padding,
* ``ensure_row_coverage`` rejects 1-D entry arrays loudly,
* bucketed plans shard (``split_equal_nnz`` / ``shard_plan``) without
  changing the aggregate.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import coo_from_dense, coo_to_scv_tiles
from repro.core.aggregate import aggregate, aggregate_scv_plan
from repro.core.formats import COOMatrix
from repro.core.partition import shard_plan, split_equal_nnz
from repro.core.scv import (
    SCVBucketedPlan,
    _coo_to_scv_tiles_loop,
    bucket_caps_for,
    bucket_tiles,
    dense_tile_threshold,
    plan_from_tiles,
    plan_from_tiles_bucketed,
    tile_nnz_histogram,
)
from repro.kernels.scv_spmm import ops as kops
from repro.kernels.scv_spmm import ref as kref
from repro.models.gnn import (
    GNNConfig,
    build_graph,
    gnn_forward,
    gnn_forward_batched,
    gnn_forward_jit,
    init_gnn,
)
from repro.serve.graph_engine import assemble_batched_graph
from repro.simul.datasets import gcn_normalize, powerlaw_graph

KINDS = ["gcn", "sage", "gin", "gat"]


def _int_coo(rng, m, n, density, dense_block=None):
    """Integer-valued sparse matrix: all partial sums exact in f32, so any
    accumulation order produces identical bits."""
    a = ((rng.random((m, n)) < density) * rng.integers(1, 5, (m, n))).astype(
        np.float32
    )
    if dense_block is not None:
        r0, c0, s = dense_block
        a[r0 : r0 + s, c0 : c0 + s] = rng.integers(1, 5, (s, s))
    return a


def _int_z(rng, n, f):
    return rng.integers(-4, 5, (n, f)).astype(np.float32)


# ---------------------------------------------------------------------------
# vector body == oracle, bit for bit
# ---------------------------------------------------------------------------
def test_vector_scalar_oracle_bit_identical(rng):
    a = _int_coo(rng, 96, 96, 0.06, dense_block=(0, 32, 32))
    z = jnp.asarray(_int_z(rng, 96, 24))
    tiles = coo_to_scv_tiles(coo_from_dense(a), 32, cap=1024)
    plan = plan_from_tiles(tiles, with_perm=False)
    assert int(np.asarray(plan.nnz_in_tile).max()) > dense_tile_threshold(32)
    out = np.asarray(kops.scv_spmm_plan(plan, z, interpret=True, body="vector"))
    ref = np.asarray(kref.scv_spmm_reference_plan(plan, z))
    np.testing.assert_array_equal(out, ref)
    np.testing.assert_array_equal(ref[:96], a @ np.asarray(z))


def test_vector_body_chunk_not_dividing_cap(rng):
    """cap gets padded up to a chunk multiple inside the kernel wrapper."""
    a = _int_coo(rng, 64, 64, 0.2)
    z = jnp.asarray(_int_z(rng, 64, 8))
    tiles = coo_to_scv_tiles(coo_from_dense(a), 16, cap=24)  # 24 % 16 != 0
    plan = plan_from_tiles(tiles, with_perm=False)
    out = np.asarray(
        kops.scv_spmm_plan(plan, z, interpret=True, body="vector", chunk=16)
    )
    np.testing.assert_array_equal(out[:64], a @ np.asarray(z))


# ---------------------------------------------------------------------------
# kernel edge cases
# ---------------------------------------------------------------------------
def test_zero_tile_bucketed_plan(rng):
    empty = coo_from_dense(np.zeros((48, 48), np.float32))
    plan = plan_from_tiles_bucketed(coo_to_scv_tiles(empty, 16))
    assert isinstance(plan, SCVBucketedPlan) and len(plan.segments) == 1
    # every tile is a coverage dummy
    assert int(np.asarray(plan.segments[0].nnz_in_tile).sum()) == 0
    z = jnp.asarray(_int_z(rng, 48, 8))
    for backend in ("jnp", "pallas_interpret"):
        out = np.asarray(aggregate_scv_plan(plan, z, backend=backend))
        assert out.shape == (48, 8) and np.all(out == 0)


def test_all_dummy_tiles_define_output(rng):
    """Edges only in block-row 0: rows 16.. are pure coverage dummies in
    every bucket segment, and each per-bucket launch must define them."""
    a = np.zeros((64, 64), np.float32)
    a[:8, :8] = _int_coo(rng, 8, 8, 0.8)
    tiles = coo_to_scv_tiles(coo_from_dense(a), 16, cap=64)
    plan = plan_from_tiles_bucketed(tiles, caps=(8, 64))
    z = jnp.asarray(_int_z(rng, 64, 12))
    out = np.asarray(aggregate_scv_plan(plan, z, backend="pallas_interpret"))
    np.testing.assert_array_equal(out, a @ np.asarray(z))


def test_nnz_equals_cap_boundary(rng):
    """A tile holding exactly cap entries sits in that bucket (no split,
    no off-by-one in the chunk loop bound)."""
    a = np.zeros((16, 16), np.float32)
    a[:4, 0] = [1, 2, 3, 4]  # tile (0,0) gets exactly 4 entries
    tiles = coo_to_scv_tiles(coo_from_dense(a), 8, cap=4)
    assert list(np.asarray(tiles.nnz_in_tile)) == [4]
    segs = bucket_tiles(tiles, (4, 8))
    assert segs[0].n_tiles == 1 and segs[1].n_tiles == 0
    plan = plan_from_tiles_bucketed(tiles, caps=(4, 8))
    z = jnp.asarray(_int_z(rng, 16, 8))
    out = np.asarray(aggregate_scv_plan(plan, z, backend="pallas_interpret"))
    np.testing.assert_array_equal(out, a @ np.asarray(z))


def test_bucket_tiles_rejects_overflowing_ladder(rng):
    a = _int_coo(rng, 16, 16, 1.0)
    tiles = coo_to_scv_tiles(coo_from_dense(a), 8, cap=64)
    with pytest.raises(ValueError, match="largest bucket"):
        bucket_tiles(tiles, (8, 16))


# ---------------------------------------------------------------------------
# bucketed segments == scalar-loop-era construction, byte for byte
# ---------------------------------------------------------------------------
def test_bucketed_segments_byte_identical_to_loop_tiles(rng):
    for trial in range(8):
        m, n = rng.integers(20, 150, 2)
        coo = coo_from_dense(_int_coo(rng, m, n, 0.1))
        caps = bucket_caps_for(tile_nnz_histogram(coo, 16), 16)
        vec = bucket_tiles(coo_to_scv_tiles(coo, 16, cap=caps[-1]), caps)
        loop = bucket_tiles(_coo_to_scv_tiles_loop(coo, 16, cap=caps[-1]), caps)
        assert len(vec) == len(loop)
        for sv, sl in zip(vec, loop):
            for f in dataclasses.fields(sv):
                a, b = getattr(sv, f.name), getattr(sl, f.name)
                if isinstance(a, np.ndarray):
                    assert a.dtype == b.dtype and np.array_equal(a, b), f.name
                else:
                    assert a == b, f.name
        # the buckets partition the entries exactly
        total = sum(s.nnz for s in vec)
        assert total == coo.nnz


# ---------------------------------------------------------------------------
# jit == eager, bucketed plan, pallas interpret
# ---------------------------------------------------------------------------
def test_bucketed_jit_eq_eager_interpret(rng):
    adj = gcn_normalize(powerlaw_graph(70, 420, seed=2))
    g = build_graph(adj, tile=32, bucket_caps=(8, 32, 128))
    z = jnp.asarray(rng.standard_normal((70, 16)).astype(np.float32))

    def f(plan, zz):
        return aggregate_scv_plan(plan, zz, backend="pallas_interpret")

    eager = np.asarray(f(g.plan, z))
    jitted = np.asarray(jax.jit(f)(g.plan, z))
    np.testing.assert_array_equal(eager, jitted)
    # dispatch integration: aggregate() accepts the bucketed plan
    np.testing.assert_array_equal(
        np.asarray(aggregate(g.plan, z, backend="jnp")),
        np.asarray(aggregate_scv_plan(g.plan, z, backend="jnp")),
    )


# ---------------------------------------------------------------------------
# all four model kinds: forward + grads through gnn_forward_jit and
# assemble_batched_graph with bucketed plans
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kind", KINDS)
def test_bucketed_forward_and_grads_all_kinds(kind, rng):
    adj = gcn_normalize(powerlaw_graph(60, 300, seed=3))
    g_b = build_graph(adj, tile=32, bucket_caps=(8, 32, 128))
    g_s = build_graph(adj, tile=32)
    assert isinstance(g_b.plan, SCVBucketedPlan)
    x = jnp.asarray(rng.standard_normal((60, 8)).astype(np.float32))
    cfg = GNNConfig(name=kind, kind=kind, d_in=8, d_hidden=8, n_classes=4,
                    backend="pallas_interpret")
    cfg_ref = dataclasses.replace(cfg, backend="jnp")
    params, _ = init_gnn(jax.random.PRNGKey(0), cfg)

    out_b = np.asarray(gnn_forward_jit(params, cfg, g_b, x))
    out_s = np.asarray(gnn_forward_jit(params, cfg_ref, g_s, x))
    np.testing.assert_allclose(out_b, out_s, atol=1e-4, rtol=1e-4)

    def loss(p, c, gg, xx):
        return (gnn_forward(p, c, gg, xx) ** 2).sum()

    grad = jax.jit(jax.grad(loss, argnums=(0, 3)), static_argnames=("c",))
    gp_b, gx_b = grad(params, cfg, g_b, x)
    gp_s, gx_s = grad(params, cfg_ref, g_s, x)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-3, rtol=1e-3
        ),
        (gp_b, gx_b), (gp_s, gx_s),
    )


@pytest.mark.parametrize("kind", ["gcn", "gat"])
def test_bucketed_composite_through_assemble(kind, rng):
    adjs = [gcn_normalize(powerlaw_graph(n, 4 * n, seed=5 + i))
            for i, n in enumerate([30, 50])]
    caps = (8, 32, 128)
    members = [build_graph(a, tile=32, bucket_caps=caps) for a in adjs]
    bg = assemble_batched_graph(members, 32, 128, with_edges=(kind == "gat"))
    assert isinstance(bg.graph.plan, SCVBucketedPlan)
    assert bg.graph.plan.caps == caps
    xs = [rng.standard_normal((a.shape[0], 8)).astype(np.float32) for a in adjs]
    cfg = GNNConfig(name=kind, kind=kind, d_in=8, d_hidden=8, n_classes=3)
    params, _ = init_gnn(jax.random.PRNGKey(1), cfg)
    outs = gnn_forward_batched(params, cfg, bg, xs)
    for a, x, o in zip(adjs, xs, outs):
        ref = gnn_forward(params, cfg, build_graph(a, tile=32), jnp.asarray(x))
        np.testing.assert_allclose(np.asarray(o), np.asarray(ref), atol=1e-4)


def test_engine_serves_bucketed_plans(rng):
    from repro.serve.graph_engine import (
        GraphEngineConfig, GraphRequest, GraphServeEngine,
    )

    cfg = GNNConfig(name="gcn", kind="gcn", d_in=8, d_hidden=8, n_classes=4)
    params, _ = init_gnn(jax.random.PRNGKey(0), cfg)
    eng = GraphServeEngine(
        {"gcn": (params, cfg)},
        GraphEngineConfig(tile=64, cap=64, bucket_caps=(8, 32, 64)),
    )
    adjs = [gcn_normalize(powerlaw_graph(40, 160, seed=8 + i)) for i in range(3)]
    xs = [rng.standard_normal((40, 8)).astype(np.float32) for _ in adjs]
    for i, (a, x) in enumerate(zip(adjs, xs)):
        eng.submit(GraphRequest(rid=i, adj=a, x=x, model="gcn"))
    done = eng.run()
    assert len(done) == 3 and all(r.done for r in done)
    for r, a, x in zip(sorted(done, key=lambda r: r.rid), adjs, xs):
        ref = gnn_forward(params, cfg, build_graph(a, tile=64), jnp.asarray(x))
        np.testing.assert_allclose(r.out, np.asarray(ref), atol=1e-4)


# ---------------------------------------------------------------------------
# satellite regressions
# ---------------------------------------------------------------------------
def test_legacy_no_nnz_grad_masks_structural_padding(rng):
    """Without nnz_in_tile, d/dvals must still be zero on padding slots
    (they alias local (0,0), where <g[0], z[0]> is generally nonzero)."""
    a = _int_coo(rng, 32, 32, 0.1)
    tiles = coo_to_scv_tiles(coo_from_dense(a), 8, cap=16)
    plan = plan_from_tiles(tiles, with_perm=False)
    z = jnp.asarray(rng.standard_normal((32, 8)).astype(np.float32))
    nnz = np.asarray(plan.nnz_in_tile)

    def loss(vv):
        out = kops.scv_spmm(
            plan.tile_row, plan.tile_col, plan.rows, plan.cols, vv, z,
            tile=8, n_rows=32, interpret=True,  # nnz_in_tile omitted
        )
        return (out ** 2).sum()

    dvals = np.asarray(jax.grad(loss)(plan.vals))
    slot = np.arange(dvals.shape[1])[None, :]
    assert np.all(dvals[slot >= nnz[:, None]] == 0), "padding slots got grads"

    def loss_ref(vv):
        out = kref.scv_spmm_reference(
            plan.tile_row, plan.tile_col, plan.rows, plan.cols, vv, z,
            tile=8, n_rows=32, nnz_in_tile=plan.nnz_in_tile,
        )
        return (out ** 2).sum()

    dref = np.asarray(jax.grad(loss_ref)(plan.vals))
    np.testing.assert_allclose(dvals, dref, atol=1e-4)


def test_ensure_row_coverage_rejects_1d():
    rows = np.zeros(5, np.int32)  # 1-D: the old code built (k, 1) pads and
    cols = np.zeros(5, np.int32)  # crashed in np.concatenate
    vals = np.zeros(5, np.float32)
    with pytest.raises(ValueError, match="2-D"):
        kops.ensure_row_coverage(
            np.zeros(5, np.int32), np.zeros(5, np.int32),
            rows, cols, vals, np.zeros(5, np.int32), 4,
        )


def test_bucketed_plan_shards_equivalently(rng):
    adj = gcn_normalize(powerlaw_graph(80, 600, seed=9))
    g = build_graph(adj, tile=16, bucket_caps=(8, 32))
    plan = g.plan
    z = jnp.asarray(rng.standard_normal((80, 8)).astype(np.float32))
    full = np.asarray(aggregate_scv_plan(plan, z, backend="jnp"))
    parts = split_equal_nnz(plan, 3)
    assert isinstance(parts, tuple) and len(parts) == len(plan.segments)
    stacked = shard_plan(plan, parts)
    assert isinstance(stacked, SCVBucketedPlan)
    # summing each part-span's aggregate reproduces the full result: shard
    # segment s into its P spans, aggregate each span, add
    acc = np.zeros_like(full)
    for seg, part in zip(stacked.segments, parts):
        width = part.part_tiles.shape[1]
        for p in range(part.n_parts):
            sl = slice(p * width, (p + 1) * width)
            acc += np.asarray(
                kref.scv_spmm_reference(
                    seg.tile_row[sl], seg.tile_col[sl], seg.rows[sl],
                    seg.cols[sl], seg.vals[sl], z,
                    tile=seg.tile, n_rows=seg.padded_shape[0],
                    nnz_in_tile=seg.nnz_in_tile[sl],
                )
            )[: full.shape[0]]
    np.testing.assert_allclose(acc, full, atol=1e-4)
