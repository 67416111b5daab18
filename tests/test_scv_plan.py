"""SCVPlan pytree + end-to-end jitted GNN forwards.

Covers the PR's acceptance criteria:

* vectorized ``coo_to_scv_tiles`` is byte-identical to the scalar loop
  emitter on randomized inputs,
* ``SCVPlan`` / ``Graph`` / ``BatchedGraph`` flatten/unflatten as pytrees
  with the documented leaf vs static-aux split,
* ``gnn_forward`` and ``gnn_forward_batched`` run under a single outer
  ``jax.jit`` (including the Pallas interpret backend on CPU) and match
  the unjitted path bit-for-bit for all four model kinds,
* jit retraces at most once per padding bucket (``_cache_size``).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.aggregate import aggregate, aggregate_scv_plan, aggregate_scv_tiles
from repro.core.formats import coo_from_dense
from repro.core.scv import (
    SCVPlan,
    _coo_to_scv_tiles_loop,
    coo_to_scv_tiles,
    plan_from_tiles,
)
from repro.models.gnn import (
    GNNConfig,
    Graph,
    build_batched_graph,
    build_graph,
    gnn_forward,
    gnn_forward_batched,
    gnn_forward_jit,
    init_gnn,
)
from repro.simul.datasets import gcn_normalize, powerlaw_graph

KINDS = ["gcn", "sage", "gin", "gat"]


def _random_coo(rng, m, n, density):
    a = ((rng.random((m, n)) < density) * rng.standard_normal((m, n))).astype(
        np.float32
    )
    return coo_from_dense(a)


# ---------------------------------------------------------------------------
# vectorized tile construction == scalar loop, byte for byte
# ---------------------------------------------------------------------------
def test_vectorized_tiles_byte_identical_to_loop(rng):
    for trial in range(25):
        m, n = rng.integers(1, 180, 2)
        density = float(rng.choice([0.0, 0.01, 0.08, 0.35]))
        coo = _random_coo(rng, m, n, density)
        tile = int(rng.choice([8, 16, 32, 64]))
        cap = [None, 8, 16][trial % 3]
        order = ["zmorton", "row_major"][trial % 2]
        vec = coo_to_scv_tiles(coo, tile, cap=cap, order=order)
        loop = _coo_to_scv_tiles_loop(coo, tile, cap=cap, order=order)
        for f in dataclasses.fields(vec):
            a, b = getattr(vec, f.name), getattr(loop, f.name)
            if isinstance(a, np.ndarray):
                assert a.dtype == b.dtype, (trial, f.name)
                assert np.array_equal(a, b), (trial, f.name)
            else:
                assert a == b, (trial, f.name)


# ---------------------------------------------------------------------------
# pytree structure
# ---------------------------------------------------------------------------
def test_scv_plan_pytree_leaf_aux_split(rng):
    coo = _random_coo(rng, 90, 90, 0.05)
    plan = plan_from_tiles(coo_to_scv_tiles(coo, 16))
    leaves, treedef = jax.tree_util.tree_flatten(plan)
    # exactly the documented array leaves; aux round-trips identically
    assert len(leaves) == 7
    rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
    assert (rebuilt.tile, rebuilt.cap, rebuilt.shape, rebuilt.order) == (
        plan.tile, plan.cap, plan.shape, plan.order,
    )
    # tree_map touches every leaf and preserves the wrapper
    doubled = jax.tree.map(lambda x: x, plan)
    assert isinstance(doubled, SCVPlan) and doubled.cap == plan.cap


def test_graph_and_batched_graph_are_pytrees(rng):
    adj = gcn_normalize(powerlaw_graph(50, 200, seed=0))
    g = build_graph(adj, tile=32)
    g2 = jax.tree.map(lambda x: x, g)
    assert isinstance(g2, Graph) and g2.n_nodes == g.n_nodes
    bg = build_batched_graph([adj, adj], tile=32, pad_nodes=128)
    bg2 = jax.tree.map(lambda x: x, bg)
    assert list(bg2.node_offsets) == list(bg.node_offsets)
    assert bg2.n_real_nodes == bg.n_real_nodes


def test_plan_aggregate_matches_tiles_backend(rng):
    coo = _random_coo(rng, 70, 70, 0.06)
    z = jnp.asarray(rng.standard_normal((70, 12)).astype(np.float32))
    tiles = coo_to_scv_tiles(coo, 16)
    plan = plan_from_tiles(tiles)
    out_plan = np.asarray(aggregate_scv_plan(plan, z, backend="jnp"))
    out_tiles = np.asarray(aggregate_scv_tiles(tiles, z, backend="jnp"))
    np.testing.assert_array_equal(out_plan, out_tiles)
    # dispatch integration
    np.testing.assert_array_equal(np.asarray(aggregate(plan, z)), out_plan)


# ---------------------------------------------------------------------------
# whole-forward jit: exact equivalence, all kinds
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kind", KINDS)
def test_jitted_forward_bit_for_bit(kind, rng):
    adj = gcn_normalize(powerlaw_graph(90, 360, seed=1))
    g = build_graph(adj, tile=32)
    x = jnp.asarray(rng.standard_normal((90, 16)).astype(np.float32))
    cfg = GNNConfig(name=kind, kind=kind, d_in=16, d_hidden=16, n_classes=4)
    params, _ = init_gnn(jax.random.PRNGKey(0), cfg)
    with jax.disable_jit():
        ref = np.asarray(gnn_forward(params, cfg, g, x))
    out = np.asarray(gnn_forward_jit(params, cfg, g, x))
    np.testing.assert_array_equal(out, ref)


@pytest.mark.parametrize("kind", KINDS)
def test_jitted_batched_forward_bit_for_bit(kind, rng):
    adjs = [gcn_normalize(powerlaw_graph(n, 4 * n, seed=2 + i))
            for i, n in enumerate([40, 70])]
    xs = [rng.standard_normal((a.shape[0], 8)).astype(np.float32) for a in adjs]
    bg = build_batched_graph(adjs, tile=32, backend_cap=32, pad_nodes=192)
    cfg = GNNConfig(name=kind, kind=kind, d_in=8, d_hidden=8, n_classes=3)
    params, _ = init_gnn(jax.random.PRNGKey(1), cfg)
    with jax.disable_jit():
        ref = gnn_forward_batched(params, cfg, bg, xs)
    fwd = jax.jit(gnn_forward_batched, static_argnames=("cfg",))
    outs = fwd(params, cfg, bg, tuple(jnp.asarray(xi) for xi in xs))
    assert len(outs) == len(ref)
    # XLA does not promise that jit and eager agree bit for bit: under jit
    # it may fuse and reassociate float sums (sage's self + neighbour add,
    # gin's (1 + eps) * h + agg).  Allow a few ulp of the output's scale.
    eps = np.finfo(np.float32).eps
    for o, r in zip(outs, ref):
        r = np.asarray(r)
        np.testing.assert_allclose(
            np.asarray(o), r, rtol=0, atol=4 * eps * np.abs(r).max()
        )


def test_jitted_forward_pallas_interpret_backend(rng):
    """Acceptance: the whole forward runs under one outer jit with the
    Pallas kernel in interpret mode on CPU — plan arrays arrive at the
    custom_vjp as tracers, not closure constants."""
    adj = gcn_normalize(powerlaw_graph(80, 320, seed=3))
    g = build_graph(adj, tile=32)
    x = jnp.asarray(rng.standard_normal((80, 8)).astype(np.float32))
    mk = lambda backend: GNNConfig(
        name="gcn", kind="gcn", d_in=8, d_hidden=8, n_classes=4, backend=backend
    )
    params, _ = init_gnn(jax.random.PRNGKey(0), mk("jnp"))
    out_p = np.asarray(gnn_forward_jit(params, mk("pallas_interpret"), g, x))
    out_r = np.asarray(gnn_forward_jit(params, mk("jnp"), g, x))
    np.testing.assert_allclose(out_p, out_r, atol=1e-5, rtol=1e-5)


def test_grad_through_jitted_pallas_plan_argument(rng):
    """The kernel's VJP must accept plan leaves as tracers (grad under an
    outer jit with the graph as an argument, not a closure constant)."""
    adj = gcn_normalize(powerlaw_graph(60, 240, seed=4))
    g = build_graph(adj, tile=32)
    x = jnp.asarray(rng.standard_normal((60, 8)).astype(np.float32))
    cfg = GNNConfig(name="gcn", kind="gcn", d_in=8, d_hidden=8, n_classes=4,
                    backend="pallas_interpret")
    cfg_ref = dataclasses.replace(cfg, backend="jnp")
    params, _ = init_gnn(jax.random.PRNGKey(0), cfg)

    def loss(p, cfg, g, x):
        return (gnn_forward(p, cfg, g, x) ** 2).sum()

    gp = jax.jit(jax.grad(loss), static_argnames=("cfg",))(params, cfg, g, x)
    gr = jax.jit(jax.grad(loss), static_argnames=("cfg",))(params, cfg_ref, g, x)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-4
        ),
        gp, gr,
    )


# ---------------------------------------------------------------------------
# retrace discipline: one trace per padding bucket
# ---------------------------------------------------------------------------
def test_jit_retraces_once_per_padding_bucket(rng):
    from repro.serve.graph_engine import (
        GraphEngineConfig, GraphRequest, GraphServeEngine,
    )

    cfg = GNNConfig(name="gcn", kind="gcn", d_in=8, d_hidden=8, n_classes=4)
    params, _ = init_gnn(jax.random.PRNGKey(0), cfg)
    eng = GraphServeEngine({"gcn": (params, cfg)}, GraphEngineConfig(tile=64, cap=64))

    def serve_wave(sizes, seed):
        adjs = [gcn_normalize(powerlaw_graph(n, 4 * n, seed=seed + i))
                for i, n in enumerate(sizes)]
        for i, a in enumerate(adjs):
            x = rng.standard_normal((a.shape[0], 8)).astype(np.float32)
            eng.submit(GraphRequest(rid=seed * 100 + i, adj=a, x=x, model="gcn"))
        eng.run()

    serve_wave([60, 90], seed=5)  # bucket 256: first trace
    base = gnn_forward_jit._cache_size()
    # different graphs, same node bucket and tile-count bucket -> NO retrace
    serve_wave([70, 80], seed=6)
    serve_wave([50, 95], seed=7)
    assert gnn_forward_jit._cache_size() == base
    # a new bucket may add at most one trace
    serve_wave([400, 500], seed=8)  # bucket 1024
    assert gnn_forward_jit._cache_size() <= base + 1


# ---------------------------------------------------------------------------
# lazy composite edges (model-kind component of the batch plan)
# ---------------------------------------------------------------------------
def test_non_gat_composite_skips_edge_arrays(rng):
    from repro.serve.graph_engine import assemble_batched_graph

    adjs = [gcn_normalize(powerlaw_graph(n, 4 * n, seed=9 + i))
            for i, n in enumerate([40, 60])]
    plans = [build_graph(a, tile=64, backend_cap=64) for a in adjs]
    lean = assemble_batched_graph(plans, 64, 128, with_edges=False)
    assert lean.graph.rows is None and lean.graph.plan.perm is None
    full = assemble_batched_graph(plans, 64, 128, with_edges=True)
    assert full.graph.rows is not None and full.graph.plan.perm is not None
    # the lean composite still aggregates identically for edge-free kinds
    xs = [rng.standard_normal((a.shape[0], 8)).astype(np.float32) for a in adjs]
    cfg = GNNConfig(name="gcn", kind="gcn", d_in=8, d_hidden=8, n_classes=4)
    params, _ = init_gnn(jax.random.PRNGKey(2), cfg)
    o1 = gnn_forward_batched(params, cfg, lean, xs)
    o2 = gnn_forward_batched(params, cfg, full, xs)
    for a, b in zip(o1, o2):
        np.testing.assert_array_equal(a, b)
    # GAT on the edge-free composite fails loudly, not silently
    cfg_gat = GNNConfig(name="gat", kind="gat", d_in=8, d_hidden=8, n_classes=4)
    params_gat, _ = init_gnn(jax.random.PRNGKey(3), cfg_gat)
    with pytest.raises(ValueError, match="with_edges"):
        gnn_forward_batched(params_gat, cfg_gat, lean, xs)


def test_engine_composite_key_carries_edge_component(rng):
    """Same member graphs under a GAT model and a GCN model must resolve
    to different composite plans (edges vs no edges) while sharing the
    member plans."""
    from repro.serve.graph_engine import (
        GraphEngineConfig, GraphRequest, GraphServeEngine,
    )

    cfg_gcn = GNNConfig(name="gcn", kind="gcn", d_in=8, d_hidden=8, n_classes=4)
    cfg_gat = GNNConfig(name="gat", kind="gat", d_in=8, d_hidden=8, n_classes=4)
    pg, _ = init_gnn(jax.random.PRNGKey(0), cfg_gcn)
    pa, _ = init_gnn(jax.random.PRNGKey(1), cfg_gat)
    eng = GraphServeEngine(
        {"gcn": (pg, cfg_gcn), "gat": (pa, cfg_gat)},
        GraphEngineConfig(tile=64, cap=64),
    )
    adjs = [gcn_normalize(powerlaw_graph(40, 160, seed=11 + i)) for i in range(2)]
    xs = [rng.standard_normal((40, 8)).astype(np.float32) for _ in adjs]
    for i, (a, x) in enumerate(zip(adjs, xs)):
        eng.submit(GraphRequest(rid=i, adj=a, x=x, model="gcn"))
    eng.run()
    m1 = eng.metrics()
    assert m1["plan_cache_misses"] == 3  # 2 members + 1 composite
    for i, (a, x) in enumerate(zip(adjs, xs)):
        eng.submit(GraphRequest(rid=10 + i, adj=a, x=x, model="gat"))
    eng.run()
    m2 = eng.metrics()
    # the GAT wave reuses both member plans (hits) but must build its own
    # composite (edge-bearing) -> exactly one new miss
    assert m2["plan_cache_misses"] == m1["plan_cache_misses"] + 1
    assert m2["plan_cache_hits"] >= m1["plan_cache_hits"] + 2
    assert all(r.done for r in eng.completed)
