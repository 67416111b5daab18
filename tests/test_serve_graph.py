"""Graph serving subsystem: block-diagonal composition, batched forward,
and the GraphServeEngine (plan cache + padding buckets + scatter-back)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.formats import COOMatrix, block_diag_coo, coo_from_dense
from repro.models.gnn import (
    BatchedGraph,
    GNNConfig,
    Graph,
    batch_features,
    build_batched_graph,
    build_graph,
    build_host_graph,
    gnn_forward,
    gnn_forward_batched,
    gnn_forward_jit,
    init_gnn,
    split_outputs,
)
from repro.serve.graph_engine import (
    GraphEngineConfig,
    GraphRequest,
    GraphServeEngine,
    _bucket_nodes,
    assemble_batched_graph,
)
from repro.simul.datasets import gcn_normalize, powerlaw_graph
from repro.stream import DeltaBatch


def _graphs(sizes, seed=0):
    return [
        gcn_normalize(powerlaw_graph(n, 4 * n, seed=seed + i))
        for i, n in enumerate(sizes)
    ]


def _features(rng, adjs, d):
    return [rng.standard_normal((a.shape[0], d)).astype(np.float32) for a in adjs]


# ---------------------------------------------------------------------------
# block_diag_coo
# ---------------------------------------------------------------------------
def test_block_diag_coo_roundtrip(rng):
    mats = [
        coo_from_dense((rng.random((m, n)) < 0.3) * rng.standard_normal((m, n)).astype(np.float32))
        for m, n in [(5, 7), (3, 3), (6, 2)]
    ]
    comp, row_off, col_off = block_diag_coo(mats)
    assert comp.shape == (14, 12)
    assert list(row_off) == [0, 5, 8, 14]
    assert list(col_off) == [0, 7, 10, 12]
    dense = comp.to_dense()
    for i, a in enumerate(mats):
        np.testing.assert_allclose(
            dense[row_off[i] : row_off[i + 1], col_off[i] : col_off[i + 1]],
            a.to_dense(),
        )
    # off-diagonal blocks are structurally empty
    assert comp.nnz == sum(a.nnz for a in mats)


def test_block_diag_coo_pad_shape():
    a = coo_from_dense(np.eye(3, dtype=np.float32))
    comp, row_off, _ = block_diag_coo([a, a], pad_shape=(10, 10))
    assert comp.shape == (10, 10)
    assert comp.nnz == 6 and list(row_off) == [0, 3, 6]
    with pytest.raises(ValueError):
        block_diag_coo([a, a], pad_shape=(4, 4))


def test_block_diag_coo_empty_list():
    comp, row_off, col_off = block_diag_coo([])
    assert comp.shape == (0, 0) and comp.nnz == 0
    assert len(row_off) == 1 and len(col_off) == 1


# ---------------------------------------------------------------------------
# batched forward == per-graph forward
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kind", ["gcn", "sage", "gin", "gat"])
def test_batched_forward_matches_per_graph(kind, rng):
    adjs = _graphs([70, 130, 50])
    xs = _features(rng, adjs, 16)
    cfg = GNNConfig(name=kind, kind=kind, d_in=16, d_hidden=16, n_classes=4)
    params, _ = init_gnn(jax.random.PRNGKey(0), cfg)
    ref = [
        np.asarray(
            gnn_forward(params, cfg, build_graph(a, tile=64, backend_cap=64), jnp.asarray(x))
        )
        for a, x in zip(adjs, xs)
    ]
    bg = build_batched_graph(adjs, tile=64, backend_cap=64, pad_nodes=512)
    outs = gnn_forward_batched(params, cfg, bg, xs)
    assert len(outs) == len(ref)
    for o, r in zip(outs, ref):
        assert o.shape == r.shape
        np.testing.assert_allclose(o, r, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("kind", ["gcn", "sage", "gin", "gat"])
def test_assembled_plan_matches_block_diag(kind, rng):
    """Engine's index-arithmetic assembly == reference block_diag build —
    including GAT, whose edge re-weighting exercises the per-member perm
    shift (entry_off) in assemble_batched_graph."""
    adjs = _graphs([60, 100, 40], seed=3)
    xs = _features(rng, adjs, 8)
    cfg = GNNConfig(name=kind, kind=kind, d_in=8, d_hidden=8, n_classes=4)
    params, _ = init_gnn(jax.random.PRNGKey(1), cfg)
    plans = [build_graph(a, tile=64, backend_cap=64) for a in adjs]
    bg = assemble_batched_graph(plans, tile=64, pad_nodes=256)
    assert bg.graph.n_nodes == 256
    outs = gnn_forward_batched(params, cfg, bg, xs)
    ref = [
        np.asarray(
            gnn_forward(params, cfg, p, jnp.asarray(x))
        )
        for p, x in zip(plans, xs)
    ]
    for o, r in zip(outs, ref):
        np.testing.assert_allclose(o, r, atol=1e-5, rtol=1e-5)


def test_bucket_nodes_ladder():
    assert _bucket_nodes(100, (256, 512), 64) == 256
    assert _bucket_nodes(300, (256, 512), 64) == 512
    # past the ladder: next power of two, not a bespoke per-size pad
    assert _bucket_nodes(600, (256, 512), 64) == 1024
    assert _bucket_nodes(5000, (256, 512), 64) == 8192


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------
def _engine(kind="gcn", **cfg_kw):
    cfg = GNNConfig(name=kind, kind=kind, d_in=8, d_hidden=8, n_classes=4)
    params, _ = init_gnn(jax.random.PRNGKey(0), cfg)
    ecfg = GraphEngineConfig(tile=64, cap=64, **cfg_kw)
    return GraphServeEngine({kind: (params, cfg)}, ecfg), params, cfg


def test_engine_outputs_match_per_graph(rng):
    adjs = _graphs([70, 130, 50, 200], seed=5)
    xs = _features(rng, adjs, 8)
    eng, params, cfg = _engine()
    for i, (a, x) in enumerate(zip(adjs, xs)):
        eng.submit(GraphRequest(rid=i, adj=a, x=x, model="gcn"))
    done = eng.run()
    assert len(done) == 4 and all(r.done for r in done)
    for r in done:
        ref = np.asarray(
            gnn_forward(
                params, cfg, build_graph(r.adj, tile=64, backend_cap=64), jnp.asarray(r.x)
            )
        )
        assert r.out.shape == (r.adj.shape[0], 4)
        np.testing.assert_allclose(r.out, ref, atol=1e-5, rtol=1e-5)


def test_engine_repeat_stream_hits_cache(rng):
    adjs = _graphs([60, 90], seed=7)
    xs = _features(rng, adjs, 8)
    eng, _, _ = _engine()
    for wave in range(3):
        for i, (a, x) in enumerate(zip(adjs, xs)):
            eng.submit(GraphRequest(rid=wave * 10 + i, adj=a, x=x, model="gcn"))
        eng.run()
    m = eng.metrics()
    # wave 1: 2 member misses + 1 composite miss; waves 2-3: composite hits
    # short-circuit everything
    assert m["plan_cache_misses"] == 3
    assert m["plan_cache_hits"] >= 2
    assert m["plan_cache_hit_rate"] > 0.3
    assert m["batches"] == 3
    # launches counts actual kernel launches: one per non-empty capacity
    # segment of the composite, times the model's layer count per wave
    assert m["launches"] % m["batches"] == 0
    assert m["launches"] // m["batches"] >= 2  # n_layers=2, >=1 segment


def test_engine_batches_bounded(rng):
    adjs = _graphs([50] * 5, seed=9)
    xs = _features(rng, adjs, 8)
    eng, _, _ = _engine(max_batch_graphs=2)
    for i, (a, x) in enumerate(zip(adjs, xs)):
        eng.submit(GraphRequest(rid=i, adj=a, x=x, model="gcn"))
    done = eng.run()
    assert len(done) == 5
    assert eng.metrics()["batches"] == 3  # ceil(5/2)


def test_engine_node_budget_counts_aligned_footprint(rng):
    # 100 raw nodes -> 128 tile-aligned; raw accounting would pack all three
    # (300 <= 300), aligned accounting packs two (384 > 300)
    adjs = _graphs([100, 100, 100], seed=17)
    xs = _features(rng, adjs, 8)
    eng, _, _ = _engine(max_batch_nodes=300)
    for i, (a, x) in enumerate(zip(adjs, xs)):
        eng.submit(GraphRequest(rid=i, adj=a, x=x, model="gcn"))
    eng.run()
    assert eng.metrics()["batches"] == 2


def test_engine_launch_count_single_cap(rng):
    # single-cap plans: exactly one kernel launch per aggregation, and the
    # gcn forward aggregates once per layer -> launches = batches * n_layers
    adjs = _graphs([60, 90], seed=33)
    xs = _features(rng, adjs, 8)
    eng, _, cfg = _engine(bucket_caps=())
    for i, (a, x) in enumerate(zip(adjs, xs)):
        eng.submit(GraphRequest(rid=i, adj=a, x=x, model="gcn"))
    eng.run()
    m = eng.metrics()
    assert m["batches"] == 1
    assert m["launches"] == cfg.n_layers


def test_plan_launches_counts_nonempty_segments():
    from repro.serve.graph_engine import plan_launches

    adj = _graphs([120], seed=35)[0]
    g_single = build_graph(adj, tile=64, backend_cap=64)
    assert plan_launches(g_single.plan) == 1
    g_bucketed = build_graph(adj, tile=64, bucket_caps=(8, 32, 128))
    segs = g_bucketed.plan.segments
    expect = sum(1 for s in segs if int(np.asarray(s.tile_row).size) > 0)
    assert plan_launches(g_bucketed.plan) == expect
    assert 1 <= expect <= 3


def test_engine_config_rejects_nonpositive_limits():
    with pytest.raises(ValueError):
        GraphEngineConfig(max_batch_graphs=0)
    with pytest.raises(ValueError):
        GraphEngineConfig(max_batch_nodes=0)
    with pytest.raises(ValueError):
        GraphEngineConfig(tile=0)
    with pytest.raises(ValueError):
        GraphEngineConfig(cap=-1)
    # a budget past the bucket ladder would unbound jit recompiles
    with pytest.raises(ValueError, match="node bucket"):
        GraphEngineConfig(max_batch_nodes=8192)
    GraphEngineConfig(max_batch_nodes=8192, node_buckets=())  # explicit opt-out


def test_engine_rejects_wrong_feature_width(rng):
    eng, _, _ = _engine()
    adj = _graphs([30], seed=19)[0]
    with pytest.raises(ValueError, match="d_in"):
        eng.submit(
            GraphRequest(
                rid=0, adj=adj, x=np.zeros((30, 5), np.float32), model="gcn"
            )
        )


def test_engine_rejects_out_of_range_indices(rng):
    # an index past the declared node count would land in a NEIGHBOR's
    # block of the composite and corrupt a co-batched request
    eng, _, _ = _engine()
    bad = COOMatrix(
        np.array([0, 1], np.int32),
        np.array([0, 70], np.int32),  # 70 >= 60
        np.ones(2, np.float32),
        (60, 60),
    )
    with pytest.raises(ValueError, match="out of range"):
        eng.submit(
            GraphRequest(rid=0, adj=bad, x=np.zeros((60, 8), np.float32), model="gcn")
        )


def test_engine_rejects_negative_indices(rng):
    # negative indices wrap in numpy fancy indexing: a -1 row would write
    # into the LAST node's aggregation, silently
    eng, _, _ = _engine()
    bad = COOMatrix(
        np.array([-1, 1], np.int32),
        np.array([0, 2], np.int32),
        np.ones(2, np.float32),
        (60, 60),
    )
    with pytest.raises(ValueError, match="non-negative"):
        eng.submit(
            GraphRequest(rid=0, adj=bad, x=np.zeros((60, 8), np.float32), model="gcn")
        )


def test_engine_rejects_nonfinite_values(rng):
    eng, _, _ = _engine()
    bad = COOMatrix(
        np.array([0, 1], np.int32),
        np.array([0, 2], np.int32),
        np.array([1.0, np.nan], np.float32),
        (60, 60),
    )
    with pytest.raises(ValueError, match="finite"):
        eng.submit(
            GraphRequest(rid=0, adj=bad, x=np.zeros((60, 8), np.float32), model="gcn")
        )


def test_engine_debug_validate_serves_clean_traffic(rng):
    # debug mode runs the full core.validate invariant chain on every
    # freshly built composite; clean traffic must be unaffected
    eng, params, cfg = _engine(debug_validate=True)
    adjs = _graphs([30, 45], seed=33)
    xs = _features(rng, adjs, 8)
    for i, (a, x) in enumerate(zip(adjs, xs)):
        eng.submit(GraphRequest(rid=i, adj=a, x=x, model="gcn"))
    done = eng.run()
    assert len(done) == 2 and all(r.done for r in done)
    ref_eng, _, _ = _engine()
    for i, (a, x) in enumerate(zip(adjs, xs)):
        ref_eng.submit(GraphRequest(rid=i, adj=a, x=x, model="gcn"))
    for r, ref in zip(done, ref_eng.run()):
        np.testing.assert_array_equal(r.out, ref.out)


def test_engine_debug_validate_catches_corrupt_plan(rng, monkeypatch):
    # corrupt the member-plan builder: debug mode must fail the wave with
    # a named invariant instead of serving wrong aggregations
    import dataclasses as _dc

    import repro.serve.graph_engine as ge
    from repro.core.validate import PlanInvariantError

    real_build = ge.build_host_graph

    def corrupt_build(*a, **k):
        g = real_build(*a, **k)
        seg0 = g.plan.segments[0]
        nnz = np.array(seg0.nnz_in_tile)
        nnz[0] = seg0.cap + 7  # cap invariant broken
        segs = (_dc.replace(seg0, nnz_in_tile=nnz),) + g.plan.segments[1:]
        return _dc.replace(g, plan=_dc.replace(g.plan, segments=segs))

    monkeypatch.setattr(ge, "build_host_graph", corrupt_build)
    eng, _, _ = _engine(debug_validate=True, max_retries=0)
    adj = _graphs([30], seed=34)[0]
    eng.submit(GraphRequest(rid=0, adj=adj, x=np.zeros((30, 8), np.float32),
                            model="gcn"))
    with pytest.raises(PlanInvariantError, match="cap"):
        eng.run()


def test_split_outputs_returns_copies(rng):
    # views would pin the bucket-sized composite for the life of each output
    adjs = _graphs([40, 40], seed=21)
    xs = _features(rng, adjs, 8)
    eng, _, _ = _engine()
    for i, (a, x) in enumerate(zip(adjs, xs)):
        eng.submit(GraphRequest(rid=i, adj=a, x=x, model="gcn"))
    done = eng.run()
    for r in done:
        assert r.out.base is None


def test_engine_node_budget_splits_batches(rng):
    adjs = _graphs([200, 200, 200], seed=11)
    xs = _features(rng, adjs, 8)
    eng, _, _ = _engine(max_batch_nodes=256)
    for i, (a, x) in enumerate(zip(adjs, xs)):
        eng.submit(GraphRequest(rid=i, adj=a, x=x, model="gcn"))
    eng.run()
    assert eng.metrics()["batches"] == 3  # each graph alone busts the budget


def test_engine_rejects_bad_requests(rng):
    eng, _, _ = _engine()
    adj = _graphs([30], seed=13)[0]
    x = rng.standard_normal((30, 8)).astype(np.float32)
    with pytest.raises(KeyError):
        eng.submit(GraphRequest(rid=0, adj=adj, x=x, model="nope"))
    with pytest.raises(ValueError):
        eng.submit(GraphRequest(rid=0, adj=adj, x=x[:10], model="gcn"))
    rect = COOMatrix(
        np.zeros(1, np.int32), np.zeros(1, np.int32), np.ones(1, np.float32), (3, 4)
    )
    with pytest.raises(ValueError):
        eng.submit(GraphRequest(rid=0, adj=rect, x=x[:3], model="gcn"))


def test_engine_failed_wave_requeues_requests(rng):
    # params built for gcn registered under a gat config: submit passes,
    # the forward raises — the wave must land back on the queue, not vanish
    cfg_bad = GNNConfig(name="gat", kind="gat", d_in=8, d_hidden=8, n_classes=4)
    cfg_gcn = GNNConfig(name="gcn", kind="gcn", d_in=8, d_hidden=8, n_classes=4)
    params, _ = init_gnn(jax.random.PRNGKey(0), cfg_gcn)
    eng = GraphServeEngine({"gat": (params, cfg_bad)}, GraphEngineConfig(tile=64, cap=64))
    adjs = _graphs([40, 40], seed=23)
    xs = _features(rng, adjs, 8)
    for i, (a, x) in enumerate(zip(adjs, xs)):
        eng.submit(GraphRequest(rid=i, adj=a, x=x, model="gat"))
    with pytest.raises(Exception):
        eng.run()
    assert sorted(r.rid for r in eng.queue) == [0, 1]
    assert not any(r.done for r in eng.queue)


def test_engine_poison_request_does_not_wedge(rng):
    # a request that fails every wave must eventually be ejected so a
    # retrying caller drains the queue instead of looping forever
    cfg_bad = GNNConfig(name="gat", kind="gat", d_in=8, d_hidden=8, n_classes=4)
    cfg_gcn = GNNConfig(name="gcn", kind="gcn", d_in=8, d_hidden=8, n_classes=4)
    params, _ = init_gnn(jax.random.PRNGKey(0), cfg_gcn)
    eng = GraphServeEngine(
        {"gat": (params, cfg_bad), "gcn": (params, cfg_gcn)},
        GraphEngineConfig(tile=64, cap=64),
    )
    adjs = _graphs([40, 40], seed=27)
    xs = _features(rng, adjs, 8)
    eng.submit(GraphRequest(rid=0, adj=adjs[0], x=xs[0], model="gat"))  # poison
    eng.submit(GraphRequest(rid=1, adj=adjs[1], x=xs[1], model="gcn"))  # healthy
    for _ in range(10):
        if not eng.queue:
            break
        try:
            eng.run()
        except Exception:
            pass
    assert not eng.queue  # drained, no wedge
    assert [r.rid for r in eng.completed] == [1]
    assert [r.rid for r in eng.failed] == [0]
    assert eng.failed[0].error is not None and not eng.failed[0].done
    assert eng.metrics()["failed"] == 1


def test_engine_equivalence_pallas_interpret_backend(rng):
    """The assembled composite must also be correct under the Pallas kernel
    semantics (PS strip zeroing on block-row change, repeated-coordinate
    padding tiles) — the jnp reference masks padding differently and would
    not catch a strip-ordering regression."""
    adjs = _graphs([60, 100], seed=25)
    xs = _features(rng, adjs, 8)
    cfg = GNNConfig(
        name="gcn", kind="gcn", d_in=8, d_hidden=8, n_classes=4,
        backend="pallas_interpret",
    )
    params, _ = init_gnn(jax.random.PRNGKey(2), cfg)
    eng = GraphServeEngine({"gcn": (params, cfg)}, GraphEngineConfig(tile=64, cap=64))
    for i, (a, x) in enumerate(zip(adjs, xs)):
        eng.submit(GraphRequest(rid=i, adj=a, x=x, model="gcn"))
    done = eng.run()
    for r in done:
        ref = np.asarray(
            gnn_forward(
                params, cfg, build_graph(r.adj, tile=64, backend_cap=64),
                jnp.asarray(r.x),
            )
        )
        np.testing.assert_allclose(r.out, ref, atol=1e-5, rtol=1e-5)


def test_engine_interrupt_consumes_no_retries(rng, monkeypatch):
    # Ctrl-C mid-wave is not a request failure: the wave is restored
    # untouched and no healthy request drifts toward ejection
    import repro.serve.graph_engine as ge

    eng, _, _ = _engine()
    adjs = _graphs([40, 40], seed=29)
    xs = _features(rng, adjs, 8)
    for i, (a, x) in enumerate(zip(adjs, xs)):
        eng.submit(GraphRequest(rid=i, adj=a, x=x, model="gcn"))

    def boom(*a, **kw):
        raise KeyboardInterrupt

    monkeypatch.setattr(ge, "gnn_forward_jit", boom)
    with pytest.raises(KeyboardInterrupt):
        eng.run()
    assert sorted(r.rid for r in eng.queue) == [0, 1]
    assert all(r.retries == 0 and not r.isolate for r in eng.queue)
    monkeypatch.undo()
    assert sorted(r.rid for r in eng.run()) == [0, 1]


def test_engine_partial_completions_survive_failed_run(rng):
    # waves completed before a failing wave must be retrievable even though
    # run() raised before returning
    cfg_bad = GNNConfig(name="gat", kind="gat", d_in=8, d_hidden=8, n_classes=4)
    cfg_gcn = GNNConfig(name="gcn", kind="gcn", d_in=8, d_hidden=8, n_classes=4)
    params, _ = init_gnn(jax.random.PRNGKey(0), cfg_gcn)
    eng = GraphServeEngine(
        {"gat": (params, cfg_bad), "gcn": (params, cfg_gcn)},
        GraphEngineConfig(tile=64, cap=64),
    )
    adjs = _graphs([40, 40], seed=31)
    xs = _features(rng, adjs, 8)
    eng.submit(GraphRequest(rid=0, adj=adjs[0], x=xs[0], model="gcn"))  # healthy
    eng.submit(GraphRequest(rid=1, adj=adjs[1], x=xs[1], model="gat"))  # poison
    with pytest.raises(Exception):
        eng.run()
    assert [r.rid for r in eng.last_completed] == [0]
    assert eng.last_completed[0].out is not None


# ---------------------------------------------------------------------------
# delta-tracked graphs: update(), revalidation, staleness
# ---------------------------------------------------------------------------
def _value_update(adj, idx, val):
    coords = [(int(adj.rows[i]), int(adj.cols[i])) for i in idx]
    return DeltaBatch.of(inserts=[(r, c, val) for r, c in coords],
                         removes=coords)


def test_engine_update_unknown_graph_raises(rng):
    eng, _, _ = _engine()
    with pytest.raises(KeyError, match="unknown graph_id"):
        eng.update("nope", DeltaBatch.of(inserts=[(0, 1, 1.0)]))
    with pytest.raises(KeyError, match="unknown graph_id"):
        eng.tracked_adj("nope")


def test_engine_tracked_adj_follows_updates(rng):
    adj = _graphs([40], seed=11)[0]
    eng, _, _ = _engine()
    x = rng.standard_normal((adj.shape[0], 8)).astype(np.float32)
    eng.submit(GraphRequest(rid=0, graph_id="g", adj=adj, x=x, model="gcn"))
    assert eng.tracked_adj("g") is adj
    d = _value_update(adj, [0], 9.0)
    eng.update("g", d)
    cur = eng.tracked_adj("g")
    assert cur is not adj and float(cur.vals[0]) == 9.0


def test_engine_tracked_request_requires_registration(rng):
    eng, _, _ = _engine()
    x = np.zeros((30, 8), np.float32)
    with pytest.raises(KeyError, match="unknown graph_id"):
        eng.submit(GraphRequest(rid=0, x=x, model="gcn", graph_id="g0"))
    with pytest.raises(ValueError, match="needs adj"):
        eng.submit(GraphRequest(rid=0, x=x, model="gcn"))


def test_engine_update_admission_mirrors_check_delta(rng):
    # check_delta runs against the *tracked* adjacency before any state
    # changes: out-of-range ids, non-finite vals, absent removes,
    # already-present inserts all bounce
    eng, _, _ = _engine()
    adj = _graphs([30], seed=41)[0]
    x = np.zeros((30, 8), np.float32)
    eng.submit(GraphRequest(rid=0, adj=adj, x=x, model="gcn", graph_id="g0"))
    eng.run()
    with pytest.raises(ValueError, match="out of range"):
        eng.update("g0", DeltaBatch.of(inserts=[(99, 0, 1.0)]))
    with pytest.raises(ValueError, match="finite"):
        eng.update("g0", DeltaBatch.of(inserts=[(0, 0, np.nan)]))
    have = set(zip(adj.rows.tolist(), adj.cols.tolist()))
    absent = next((r, c) for r in range(30) for c in range(30)
                  if (r, c) not in have)
    with pytest.raises(ValueError, match="absent edge"):
        eng.update("g0", DeltaBatch.of(removes=[absent]))
    r0, c0 = int(adj.rows[0]), int(adj.cols[0])
    with pytest.raises(ValueError, match="already-present"):
        eng.update("g0", DeltaBatch.of(inserts=[(r0, c0, 1.0)]))
    with pytest.raises(ValueError, match="duplicate insert"):
        eng.update("g0", DeltaBatch.of(
            inserts=[(absent[0], absent[1], 1.0), (absent[0], absent[1], 2.0)]
        ))
    # nothing landed: the tracked state is untouched
    assert eng.metrics()["graph_updates"] == 0


def test_engine_submit_update_submit_serves_post_delta(rng):
    # the staleness fix: after update(), a tracked request must be served
    # from the post-delta adjacency — never a stale cached plan
    adj = _graphs([50], seed=43)[0]
    x = rng.standard_normal((50, 8)).astype(np.float32)
    eng, params, cfg = _engine()
    eng.submit(GraphRequest(rid=0, adj=adj, x=x, model="gcn", graph_id="g0"))
    out_pre = eng.run()[0].out

    d = _value_update(adj, [0, 1, 2], 3.5)
    eng.update("g0", d)
    eng.submit(GraphRequest(rid=1, x=x, model="gcn", graph_id="g0"))
    out_post = eng.run()[0].out

    from repro.stream import apply_coo

    final = apply_coo(adj, d)
    bucket_caps = tuple(eng.cfg.bucket_caps) or None
    ref = np.asarray(gnn_forward(
        params, cfg,
        build_graph(final, tile=64,
                    backend_cap=None if bucket_caps else eng.cfg.cap,
                    bucket_caps=bucket_caps),
        jnp.asarray(x),
    ))
    np.testing.assert_allclose(out_post, ref, atol=1e-5, rtol=1e-5)
    assert np.abs(out_post - out_pre).max() > 0  # the delta is visible
    m = eng.metrics()
    assert m["plan_cache_revalidated"] == 1  # patched, not a full miss
    assert m["graph_updates"] == 1


def test_engine_update_between_submit_and_run(rng):
    # adjacency resolves at wave time: an update landing after submit but
    # before run() is reflected in the served output
    adj = _graphs([50], seed=47)[0]
    x = rng.standard_normal((50, 8)).astype(np.float32)
    eng, params, cfg = _engine()
    eng.submit(GraphRequest(rid=0, adj=adj, x=x, model="gcn", graph_id="g0"))
    eng.run()
    d = _value_update(adj, [0, 1], 9.0)
    eng.submit(GraphRequest(rid=1, x=x, model="gcn", graph_id="g0"))
    eng.update("g0", d)  # lands while rid=1 is queued
    out = eng.run()[0].out

    from repro.stream import apply_coo

    bucket_caps = tuple(eng.cfg.bucket_caps) or None
    ref = np.asarray(gnn_forward(
        params, cfg,
        build_graph(apply_coo(adj, d), tile=64,
                    backend_cap=None if bucket_caps else eng.cfg.cap,
                    bucket_caps=bucket_caps),
        jnp.asarray(x),
    ))
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


def test_engine_periodic_reanchor_rejoins_content_keys(rng):
    # after anchor_every updates the tracked key must re-home to the
    # coo_content_key of the current adjacency, so an untracked client
    # submitting the identical post-delta graph hits the same entry
    adj = _graphs([50], seed=47)[0]
    x = rng.standard_normal((50, 8)).astype(np.float32)
    eng, params, cfg = _engine(anchor_every=3)
    eng.submit(GraphRequest(rid=0, adj=adj, x=x, model="gcn", graph_id="g0"))
    eng.run()

    cur = adj
    for i in range(3):
        d = _value_update(cur, [i, i + 1], 2.0 + i)
        key = eng.update("g0", d)
        from repro.stream import apply_coo

        cur = apply_coo(cur, d)
    # third update crossed the anchor threshold: key == content key now
    assert key == eng._member_content_key(cur)
    m = eng.metrics()
    assert m["plan_cache_anchored"] == 1
    # anchored updates still count as revalidations (the Phase B gate)
    assert m["plan_cache_revalidated"] == 3 == m["graph_updates"]
    # the anchored entry is live under the content key: an untracked
    # submit of the same adjacency resolves without a member rebuild
    misses_before = m["plan_cache_misses"]
    eng.submit(GraphRequest(rid=1, adj=cur, x=x, model="gcn"))
    out = eng.run()[0].out
    # one composite miss is expected (new batch), but no member miss
    assert eng.metrics()["plan_cache_misses"] == misses_before + 1
    bucket_caps = tuple(eng.cfg.bucket_caps) or None
    ref = np.asarray(gnn_forward(
        params, cfg,
        build_graph(cur, tile=64,
                    backend_cap=None if bucket_caps else eng.cfg.cap,
                    bucket_caps=bucket_caps),
        jnp.asarray(x),
    ))
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


def test_engine_anchor_disabled_keeps_lineage_keys(rng):
    adj = _graphs([50], seed=48)[0]
    x = rng.standard_normal((50, 8)).astype(np.float32)
    eng, _, _ = _engine(anchor_every=0)
    eng.submit(GraphRequest(rid=0, adj=adj, x=x, model="gcn", graph_id="g0"))
    eng.run()
    for i in range(4):
        key = eng.update("g0", _value_update(eng.tracked_adj("g0"), [i], 1.5))
    assert eng.metrics()["plan_cache_anchored"] == 0
    assert key != eng._member_content_key(eng.tracked_adj("g0"))


def test_engine_update_invalidates_composite_batches(rng):
    # composite keys combine member keys, so a delta on one tracked member
    # re-keys every batch it rides in — co-batched outputs stay fresh
    adjs = _graphs([40, 40], seed=53)
    xs = _features(rng, adjs, 8)
    eng, params, cfg = _engine()
    eng.submit(GraphRequest(rid=0, adj=adjs[0], x=xs[0], model="gcn",
                            graph_id="g0"))
    eng.submit(GraphRequest(rid=1, adj=adjs[1], x=xs[1], model="gcn"))
    eng.run()

    d = _value_update(adjs[0], [0, 1, 2, 3], 5.0)
    eng.update("g0", d)
    eng.submit(GraphRequest(rid=2, x=xs[0], model="gcn", graph_id="g0"))
    eng.submit(GraphRequest(rid=3, adj=adjs[1], x=xs[1], model="gcn"))
    done = {r.rid: r.out for r in eng.run()}

    from repro.stream import apply_coo

    bucket_caps = tuple(eng.cfg.bucket_caps) or None
    ref = np.asarray(gnn_forward(
        params, cfg,
        build_graph(apply_coo(adjs[0], d), tile=64,
                    backend_cap=None if bucket_caps else eng.cfg.cap,
                    bucket_caps=bucket_caps),
        jnp.asarray(xs[0]),
    ))
    np.testing.assert_allclose(done[2], ref, atol=1e-5, rtol=1e-5)


def test_engine_reregister_resets_tracked_state(rng):
    # a request carrying both adj and graph_id resets the tracked graph
    adj = _graphs([40], seed=59)[0]
    x = rng.standard_normal((40, 8)).astype(np.float32)
    eng, _, _ = _engine()
    eng.submit(GraphRequest(rid=0, adj=adj, x=x, model="gcn", graph_id="g0"))
    eng.run()
    eng.update("g0", _value_update(adj, [0], 2.0))
    key_after_update = eng._graphs["g0"].key
    eng.submit(GraphRequest(rid=1, adj=adj, x=x, model="gcn", graph_id="g0"))
    assert eng._graphs["g0"].key != key_after_update  # back to content key
    out = eng.run()[0].out
    assert np.isfinite(out).all()


def test_engine_empty_delta_is_a_noop(rng):
    adj = _graphs([40], seed=61)[0]
    x = np.zeros((40, 8), np.float32)
    eng, _, _ = _engine()
    eng.submit(GraphRequest(rid=0, adj=adj, x=x, model="gcn", graph_id="g0"))
    eng.run()
    key = eng._graphs["g0"].key
    assert eng.update("g0", DeltaBatch.of()) == key
    assert eng.metrics()["graph_updates"] == 0


def test_engine_mixed_model_kinds_batch_separately(rng):
    cfg_a = GNNConfig(name="gcn", kind="gcn", d_in=8, d_hidden=8, n_classes=4)
    cfg_b = GNNConfig(name="gin", kind="gin", d_in=8, d_hidden=8, n_classes=4)
    pa, _ = init_gnn(jax.random.PRNGKey(0), cfg_a)
    pb, _ = init_gnn(jax.random.PRNGKey(1), cfg_b)
    eng = GraphServeEngine(
        {"gcn": (pa, cfg_a), "gin": (pb, cfg_b)},
        GraphEngineConfig(tile=64, cap=64),
    )
    adjs = _graphs([40, 40, 40, 40], seed=15)
    xs = _features(rng, adjs, 8)
    for i, (a, x) in enumerate(zip(adjs, xs)):
        eng.submit(GraphRequest(rid=i, adj=a, x=x, model="gcn" if i % 2 else "gin"))
    done = eng.run()
    assert len(done) == 4
    assert eng.metrics()["batches"] == 2  # one per kind
    for r in done:
        assert r.out.shape == (40, 4) and np.isfinite(r.out).all()


# ---------------------------------------------------------------------------
# member plans on the host, one put per composite
# ---------------------------------------------------------------------------
def _layout(ladder: bool) -> dict:
    return {"bucket_caps": (8, 32, 128)} if ladder else {"backend_cap": 64}


def _assert_leaves_equal(a, b):
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("with_edges", [False, True], ids=["gcn", "gat"])
@pytest.mark.parametrize("ladder", [True, False], ids=["ladder", "single_cap"])
def test_host_members_assemble_to_the_device_composite(ladder, with_edges):
    """Host-stage members (numpy leaves) and device-leaf members of the
    same graphs assemble to the same composite, leaf for leaf; both
    composites come out on the device."""
    adjs = _graphs([70, 130, 50], seed=21)
    host = [build_host_graph(a, tile=64, **_layout(ladder)) for a in adjs]
    dev = [build_graph(a, tile=64, **_layout(ladder)) for a in adjs]
    for h, d in zip(host, dev):
        assert all(
            isinstance(x, np.ndarray) for x in jax.tree_util.tree_leaves(h)
        )
        assert all(isinstance(x, jax.Array) for x in jax.tree_util.tree_leaves(d))
        _assert_leaves_equal(h, d)  # build_graph is the host stage, put
    bg_host = assemble_batched_graph(host, 64, 512, with_edges=with_edges)
    bg_dev = assemble_batched_graph(dev, 64, 512, with_edges=with_edges)
    _assert_leaves_equal(bg_host, bg_dev)
    leaves = jax.tree_util.tree_leaves(bg_host.graph)
    assert leaves and all(isinstance(x, jax.Array) for x in leaves)
    assert (bg_host.graph.rows is not None) == with_edges


def _device_path_outputs(eng, params, cfg, reqs, adjs):
    """The wave served the way the engine did before member plans moved
    to the host: device-leaf members through ``build_graph``, assembled
    at the engine's padding bucket, one jitted forward."""
    (bg,) = [
        v for v in map(eng.plan_cache.peek, eng.plan_cache.keys)
        if isinstance(v, BatchedGraph)
    ]
    members = [build_graph(a, tile=64, bucket_caps=eng.cfg.bucket_caps)
               for a in adjs]
    ref_bg = assemble_batched_graph(
        members, 64, bg.graph.n_nodes, with_edges=cfg.kind == "gat"
    )
    _assert_leaves_equal(bg, ref_bg)
    out = gnn_forward_jit(
        params, cfg, ref_bg.graph, batch_features(ref_bg, [r.x for r in reqs])
    )
    return split_outputs(ref_bg, np.asarray(out)), bg


@pytest.mark.parametrize("kind", ["gcn", "gat"])
def test_engine_keeps_member_plans_on_host(kind, rng):
    """A wave of distinct graphs: cached members hold numpy leaves, no
    plan array comes back from the device, one composite's leaves go
    out, and the outputs equal the device-member path's bit for bit."""
    adjs = _graphs([40, 70, 30, 90, 50], seed=23)
    xs = _features(rng, adjs, 8)
    eng, params, cfg = _engine(kind)
    reqs = [
        eng.submit(GraphRequest(rid=i, adj=a, x=x, model=kind))
        for i, (a, x) in enumerate(zip(adjs, xs))
    ]
    assert len(eng.run()) == len(adjs)
    members = [
        v for v in map(eng.plan_cache.peek, eng.plan_cache.keys)
        if isinstance(v, Graph)
    ]
    assert len(members) == len(adjs)
    for g in members:
        assert all(
            isinstance(x, np.ndarray) for x in jax.tree_util.tree_leaves(g)
        )
    ref, bg = _device_path_outputs(eng, params, cfg, reqs, adjs)
    m = eng.metrics()
    assert m["plan_leaf_fetches"] == 0
    assert m["plan_leaf_puts"] == len(jax.tree_util.tree_leaves(bg.graph))
    for r, o in zip(reqs, ref):
        np.testing.assert_array_equal(r.out, o)


def test_engine_update_patches_host_member(rng):
    """A delta on a tracked graph patches its cached host member in
    place of a rebuild; the next wave equals a fresh engine's build of
    the post-delta graph, and the member stays on the host."""
    from repro.stream import apply_coo

    adj = _graphs([60], seed=25)[0]
    x = rng.standard_normal((60, 8)).astype(np.float32)
    eng, params, cfg = _engine()
    eng.submit(GraphRequest(rid=0, adj=adj, x=x, model="gcn", graph_id="g0"))
    eng.run()
    d = _value_update(adj, [0, 3, 5], 4.5)
    key = eng.update("g0", d)
    assert eng.metrics()["plan_cache_revalidated"] == 1
    patched = eng.plan_cache.peek(key)
    assert all(
        isinstance(v, np.ndarray) for v in jax.tree_util.tree_leaves(patched)
    )
    eng.submit(GraphRequest(rid=1, x=x, model="gcn", graph_id="g0"))
    out = eng.run()[0].out

    fresh, _, _ = _engine()
    fresh.submit(GraphRequest(rid=0, adj=apply_coo(adj, d), x=x, model="gcn"))
    np.testing.assert_array_equal(out, fresh.run()[0].out)
    _assert_leaves_equal(
        patched, build_host_graph(apply_coo(adj, d), tile=64,
                                  bucket_caps=eng.cfg.bucket_caps),
    )
    assert eng.metrics()["plan_leaf_fetches"] == 0
