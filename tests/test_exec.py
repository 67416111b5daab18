"""The plan executor (core/exec.py): decision rule, ShardedPlan pytree
contract, single-device placement equivalence, and — in an 8-fake-device
subprocess (flags must be set before jax initializes) — multi-device
parity: bucketed shard_map == single-device bucketed == jnp reference bit
for bit, Z-sharded == replicated, engine sharded composite == unsharded
engine, and grad parity through the sharded path for all four model
kinds."""
import json
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (
    PlanExecutor,
    ShardedPlan,
    ShardingDecision,
    coo_to_scv_tiles,
    decide_sharding,
    load_imbalance,
    plan_from_tiles,
    plan_from_tiles_bucketed,
    shard_plan,
    split_equal_nnz,
)
from repro.core.aggregate import aggregate, aggregate_scv_plan
from repro.core.dist import DistributedGraph, distribute_plan
from repro.simul.datasets import gcn_normalize, powerlaw_graph


# ---------------------------------------------------------------------------
# decision rule
# ---------------------------------------------------------------------------
def test_decide_sharding_axes():
    # plenty of nnz AND a 2-way-splittable feature width: the byte model
    # balances both axes (t4f2 moves fewer bytes/device than t8f1 — the
    # allreduce term grows with tp while the gather term shrinks)
    assert decide_sharding(10**6, 256, 8) == ShardingDecision("2d", 4, 2)
    # tiny graph, wide features: all devices to the feature axis
    assert decide_sharding(100, 1024, 8) == ShardingDecision("features", 1, 8)
    # both floors bind partway: 2-D
    d = decide_sharding(20_000, 256, 8)
    assert d.kind == "2d" and d.tile_parts == 4 and d.feature_parts == 2
    # the feature floor is one full kernel feature block: a 512-col Z only
    # splits 4 ways even with devices to spare
    assert decide_sharding(100, 512, 8).feature_parts == 4
    # nothing to shard
    assert decide_sharding(10, 4, 8).kind == "replicated"
    assert decide_sharding(10**6, 256, 1).kind == "replicated"
    # a known row count sharpens the model: dense-ish graphs (high avg
    # degree -> small out slab) tilt back toward pure tile spans
    dense = decide_sharding(10**6, 256, 8, n_rows=2_000)
    assert dense.tile_parts > decide_sharding(10**6, 256, 8).tile_parts // 2


def test_placement_bytes_model():
    from repro.core.exec import placement_bytes

    pb = placement_bytes(10**6, 256, 4, 2, n_rows=125_000)
    # components add up, and the psum term vanishes at tp == 1
    assert pb["total"] == pb["plan"] + pb["z_gather"] + pb["out"] + pb[
        "collective"
    ]
    assert pb["resident"] == pb["plan"] + pb["z_slab"] + pb["out"]
    assert placement_bytes(10**6, 256, 1, 2)["collective"] == 0
    # the tile axis divides plan + gather; the feature axis divides slabs
    half = placement_bytes(10**6, 256, 8, 2, n_rows=125_000)
    assert half["plan"] == pb["plan"] / 2 and half["z_gather"] == pb[
        "z_gather"
    ] / 2
    assert half["out"] == pb["out"]


@pytest.fixture(scope="module")
def sparse_bucketed():
    """131k-node, 1M-edge power-law graph, bucketed at its own ladder."""
    from repro.core.scv import bucket_caps_for, tile_nnz_histogram

    adj = powerlaw_graph(1 << 17, 1_000_000, seed=0)
    caps = bucket_caps_for(tile_nnz_histogram(adj, 64), 64)
    plan = plan_from_tiles_bucketed(
        coo_to_scv_tiles(adj, 64, cap=caps[-1]), caps=caps
    )
    assert len(plan.segments) > 1
    return adj, plan


@pytest.mark.parametrize("tp,fp", [(8, 1), (1, 8), (4, 2)])
def test_placement_bytes_match_placed_plan(sparse_bucketed, tp, fp):
    """The byte model's per-device resident bytes (launched slots priced,
    ``n_slots``) stay within [0.95, 1.10] of a placed plan's real leaves:
    the residual is only the integer tile-boundary split of the spans."""
    from repro.core.exec import placement_bytes
    from repro.tune import plan_launched_slots

    adj, plan = sparse_bucketed
    n, f = adj.shape[0], 64
    stacked = shard_plan(plan, split_equal_nnz(plan, tp))
    plan_bytes = sum(
        s.rows.nbytes + s.cols.nbytes + s.vals.nbytes for s in stacked.segments
    ) / tp
    actual = plan_bytes + 2 * n * f * 4 / fp  # + Z slab + output slab
    pred = placement_bytes(
        adj.nnz, f, tp, fp, n_rows=n, n_slots=plan_launched_slots(plan)
    )
    assert 0.95 <= actual / pred["resident"] <= 1.10


def test_decision_validation():
    with pytest.raises(ValueError):
        ShardingDecision("2d", 1, 4)  # 2d needs both axes > 1
    with pytest.raises(ValueError):
        ShardingDecision("replicated", 2, 1)
    with pytest.raises(ValueError):
        ShardingDecision("sideways", 2, 1)
    # degenerate 1-span tile placement is legal (distribute_plan(n_parts=1))
    ShardingDecision("tiles", 1, 1)


def test_decision_signature_stable():
    assert ShardingDecision("2d", 4, 2).signature == "2d:t4f2"


# ---------------------------------------------------------------------------
# placement on one device (mesh (1, 1)): pure layout equivalence
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def graph_and_plans():
    adj = gcn_normalize(powerlaw_graph(500, 3000, seed=0))
    tiles = coo_to_scv_tiles(adj, 32, cap=64)
    return (
        adj,
        plan_from_tiles(tiles),
        plan_from_tiles_bucketed(tiles, caps=(8, 32, 64)),
    )


def test_distribute_plan_accepts_bucketed(graph_and_plans):
    """The PR-4 TypeError escape hatch is gone: bucketed plans place."""
    adj, plan, bplan = graph_and_plans
    rng = np.random.default_rng(0)
    z = jnp.asarray(rng.standard_normal((adj.shape[1], 16)).astype(np.float32))
    ref = np.asarray(aggregate_scv_plan(plan, z, backend="jnp"))
    for p in (plan, bplan):
        sp = distribute_plan(p, 1)
        assert isinstance(sp, DistributedGraph)  # == ShardedPlan
        assert len(sp.segments) == (len(bplan.segments) if p is bplan else 1)
        out = np.asarray(aggregate_scv_plan(sp, z, backend="jnp"))
        np.testing.assert_allclose(out, ref, atol=1e-4)
        # format dispatch through the generic entry point too
        out2 = np.asarray(aggregate(sp, z, backend="jnp"))
        np.testing.assert_allclose(out2, ref, atol=1e-4)


def test_sharded_plan_pytree_roundtrip(graph_and_plans):
    _, _, bplan = graph_and_plans
    sp = distribute_plan(bplan, 1)
    leaves, treedef = jax.tree.flatten(sp)
    sp2 = jax.tree.unflatten(treedef, leaves)
    assert sp2.decision == sp.decision and sp2.mesh == sp.mesh
    assert sp2.caps == sp.caps and sp2.shape == sp.shape


def test_sharded_plan_reweighted(graph_and_plans):
    adj, _, bplan = graph_and_plans
    rng = np.random.default_rng(1)
    z = jnp.asarray(rng.standard_normal((adj.shape[1], 8)).astype(np.float32))
    ev = jnp.asarray(rng.standard_normal(adj.nnz).astype(np.float32))
    ref = np.asarray(aggregate_scv_plan(bplan.reweighted(ev), z, backend="jnp"))
    sp = distribute_plan(bplan, 1)
    out = np.asarray(aggregate_scv_plan(sp.reweighted(ev), z, backend="jnp"))
    np.testing.assert_allclose(out, ref, atol=1e-4)


def test_load_imbalance_per_segment(graph_and_plans):
    _, plan, bplan = graph_and_plans
    part = split_equal_nnz(bplan, 4)
    per = load_imbalance(part, per_segment=True)
    assert len(per) == len(bplan.segments) and all(r >= 1.0 for r in per)
    # the flat aggregate is nnz-weighted across segments, not the mean of
    # the per-segment ratios — both views must be available
    flat = load_imbalance(part)
    assert flat >= 1.0
    # single-cap partitions report a 1-tuple
    assert len(load_imbalance(split_equal_nnz(plan, 4), per_segment=True)) == 1
    # the placed plan exposes the same breakdown
    sp = distribute_plan(bplan, 1)
    assert len(sp.imbalance_per_segment) == len(bplan.segments)
    assert sp.imbalance == pytest.approx(1.0)  # one part holds everything


def test_prepare_replicated_is_identity(graph_and_plans):
    _, plan, _ = graph_and_plans
    ex = PlanExecutor()
    assert ex.prepare(plan, decision=ShardingDecision("replicated")) is plan


# ---------------------------------------------------------------------------
# 8 fake devices: the real multi-device parity matrix (subprocess)
# ---------------------------------------------------------------------------
PARITY_SCRIPT = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import json
import numpy as np, jax, jax.numpy as jnp
from repro.core import (PlanExecutor, ShardingDecision, coo_to_scv_tiles,
                        plan_from_tiles_bucketed)
from repro.core.aggregate import aggregate_scv_plan
from repro.core.dist import aggregate_distributed, distribute_plan
from repro.core.formats import COOMatrix
from repro.kernels.scv_spmm.ref import scv_spmm_reference_plan
from repro.simul.datasets import powerlaw_graph

res = {}
adj = powerlaw_graph(700, 5000, seed=0)
rng = np.random.default_rng(0)
# integer-valued inputs: psum/segment reassociation stays exact in f32
adj = COOMatrix(adj.rows, adj.cols,
                rng.integers(-3, 4, adj.nnz).astype(np.float32), adj.shape)
tiles = coo_to_scv_tiles(adj, 32, cap=64)
bplan = plan_from_tiles_bucketed(tiles, caps=(8, 32, 64))
z = jnp.asarray(rng.integers(-3, 4, (adj.shape[1], 48)).astype(np.float32))
single = np.asarray(aggregate_scv_plan(bplan, z, backend="jnp"))
ref = np.asarray(scv_spmm_reference_plan(bplan, z))[: adj.shape[0]]
res["single_eq_ref"] = bool((single == ref).all())

ex = PlanExecutor()
for dec in (ShardingDecision("tiles", 8, 1),
            ShardingDecision("features", 1, 8),
            ShardingDecision("2d", 4, 2)):
    sp = ex.prepare(bplan, decision=dec)
    out = np.asarray(aggregate_scv_plan(sp, z, backend="jnp"))
    res[f"bit_{dec.kind}"] = bool((out == single).all())
    res[f"imb_{dec.kind}"] = sp.imbalance

# compat entry point (bucketed through distribute_plan/aggregate_distributed)
g = distribute_plan(bplan, 8)
res["bit_dist_api"] = bool(
    (np.asarray(aggregate_distributed(g, z)) == single).all()
)

# the Pallas kernel body under shard_map (interpret mode): span padding
# repeats the last tile's coordinates and unvisited strips are masked, so
# the kernel path agrees bit for bit too
sp = ex.prepare(bplan, decision=ShardingDecision("tiles", 8, 1))
out_k = np.asarray(aggregate_scv_plan(sp, z, backend="pallas_interpret"))
single_k = np.asarray(aggregate_scv_plan(bplan, z, backend="pallas_interpret"))
res["bit_pallas"] = bool((out_k == single_k).all())
res["bit_pallas_vs_ref"] = bool((out_k == single).all())
print(json.dumps(res))
'''


GNN_SCRIPT = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import json
import numpy as np, jax, jax.numpy as jnp
from repro.core.exec import PlanExecutor, ShardingDecision
from repro.models.gnn import GNNConfig, build_graph, gnn_forward_jit, init_gnn
from repro.serve.graph_engine import (GraphEngineConfig, GraphRequest,
                                      GraphServeEngine)
from repro.simul.datasets import gcn_normalize, powerlaw_graph

res = {}
rng = np.random.default_rng(0)
adj = gcn_normalize(powerlaw_graph(400, 2400, seed=1))
x = jnp.asarray(rng.standard_normal((adj.shape[0], 16)).astype(np.float32))
ex = PlanExecutor(min_nnz_per_part=64, min_features_per_part=4)

for kind in ("gcn", "sage", "gin", "gat"):
    cfg = GNNConfig(name=kind, kind=kind, d_in=16, d_hidden=16, n_classes=4)
    params, _ = init_gnn(jax.random.PRNGKey(0), cfg)
    g = build_graph(adj, tile=64, bucket_caps=(8, 32, 64))
    g_sharded = ex.prepare_graph(g, decision=ShardingDecision("2d", 4, 2))
    out = np.asarray(gnn_forward_jit(params, cfg, g, x))
    out_s = np.asarray(gnn_forward_jit(params, cfg, g_sharded, x))
    res[f"fwd_{kind}"] = float(np.abs(out - out_s).max())

    def loss(p, graph):
        return jnp.sum(gnn_forward_jit(p, cfg, graph, x) ** 2)

    gr = jax.grad(loss)(params, g)
    gr_s = jax.grad(loss)(params, g_sharded)
    res[f"grad_{kind}"] = max(
        float(np.abs(a - b).max())
        for a, b in zip(jax.tree.leaves(gr), jax.tree.leaves(gr_s))
    )

# engine: over-threshold composites route through the executor
adjs = [gcn_normalize(powerlaw_graph(n, 4 * n, seed=i))
        for i, n in enumerate([300, 500, 800])]
cfg = GNNConfig(name="gcn", kind="gcn", d_in=16, d_hidden=16, n_classes=4)
params, _ = init_gnn(jax.random.PRNGKey(0), cfg)
xs = [rng.standard_normal((a.shape[0], 16)).astype(np.float32) for a in adjs]

def serve(ecfg, executor=None):
    eng = GraphServeEngine({"gcn": (params, cfg)}, ecfg, executor=executor)
    for i, (a, xi) in enumerate(zip(adjs, xs)):
        eng.submit(GraphRequest(rid=i, adj=a, x=xi, model="gcn"))
    eng.run()
    return eng, {r.rid: r.out for r in eng.completed}

base = dict(tile=64, max_batch_nodes=2048, node_buckets=(512, 1024, 2048))
_, plain = serve(GraphEngineConfig(**base))
eng, shard = serve(
    GraphEngineConfig(**base, shard_nnz_threshold=1000),
    executor=PlanExecutor(min_nnz_per_part=256, min_features_per_part=8),
)
res["engine_sharded_batches"] = eng.metrics()["sharded_batches"]
res["engine_err"] = max(
    float(np.abs(plain[r] - shard[r]).max()) for r in plain
)
# hot oversized batch: the cached composite reuses its sharded layout
h0 = eng.plan_cache.stats.hits
for i, (a, xi) in enumerate(zip(adjs, xs)):
    eng.submit(GraphRequest(rid=10 + i, adj=a, x=xi, model="gcn"))
eng.run()
res["engine_repeat_hits"] = eng.plan_cache.stats.hits - h0
print(json.dumps(res))
'''


def _run(script):
    res = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        cwd=".", timeout=600,
    )
    assert res.returncode == 0, res.stderr[-3000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


def test_sharded_aggregation_parity_8_devices():
    """Bucketed shard_map == single-device bucketed == jnp reference, bit
    for bit, for tile-span, feature-axis, and 2-D sharding."""
    r = _run(PARITY_SCRIPT)
    assert r["single_eq_ref"], r
    for kind in ("tiles", "features", "2d"):
        assert r[f"bit_{kind}"], r
        assert r[f"imb_{kind}"] < 1.5, r
    assert r["bit_dist_api"], r
    assert r["bit_pallas"] and r["bit_pallas_vs_ref"], r


def test_sharded_gnn_and_engine_8_devices():
    """Forward + grad parity through the sharded path for all four model
    kinds; engine routes over-threshold composites through the executor
    with matching output and reuses the cached sharded layout."""
    r = _run(GNN_SCRIPT)
    for kind in ("gcn", "sage", "gin", "gat"):
        assert r[f"fwd_{kind}"] < 1e-4, r
        assert r[f"grad_{kind}"] < 1e-3, r
    assert r["engine_sharded_batches"] > 0, r
    assert r["engine_err"] < 1e-4, r
    assert r["engine_repeat_hits"] >= 1, r
