"""Coverage-free accumulator-chained launches (DESIGN.md §2).

The bucketed executor used to launch each capacity segment as an
independent zero-initialized kernel and sum the partial outputs — every
segment therefore had to carry its own coverage-dummy tail so unvisited
PS strips were defined.  Segments now chain through ONE output
accumulator (``input_output_aliases``): segment 0 runs in legacy
zero-init mode and its coverage tail defines the whole output; segments
1+ seed each visited strip from the accumulator and pass unvisited
strips through.

Acceptance criteria covered here:

* coverage dummies exist exactly once per plan (segment 0 only),
* the chained forward is byte-identical to the per-segment-sum
  reference on integer inputs, for plain plans and through all four
  model kinds,
* grads (dvals / dZ) flow through the chain and match the reference
  autodiff,
* ``init="zeros"`` (the sharded-span mode: explicit zero accumulator,
  no coverage anywhere) matches too,
* sharded execution (tiles / features / 2-D meshes) of coverage-free
  plans stays on the oracle, and ``validate_plan`` stays green.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import coo_from_dense, coo_to_scv_tiles
from repro.core.aggregate import aggregate_scv_plan
from repro.core.exec import PlanExecutor, ShardingDecision
from repro.core.scv import SCVBucketedPlan, bucket_tiles, plan_from_tiles_bucketed
from repro.core.validate import validate_plan
from repro.kernels.scv_spmm import ops as kops
from repro.kernels.scv_spmm import ref as kref
from repro.models.gnn import GNNConfig, build_graph, gnn_forward, init_gnn
from repro.simul.datasets import gcn_normalize, powerlaw_graph

KINDS = ["gcn", "sage", "gin", "gat"]


def _int_coo(rng, m, n, density):
    a = ((rng.random((m, n)) < density) * rng.integers(1, 5, (m, n))).astype(
        np.float32
    )
    return a


def _bucketed(rng, m=128, density=0.08, tile=16, caps=(8, 32, 128)):
    a = _int_coo(rng, m, m, density)
    coo = coo_from_dense(a)
    tiles = coo_to_scv_tiles(coo, tile, cap=max(caps))
    plan = plan_from_tiles_bucketed(tiles, caps)
    return a, coo, plan


def _dummy_counts(plan):
    """Coverage-dummy (zero-nnz) tile count per segment."""
    return [
        int((np.asarray(s.nnz_in_tile) == 0).sum()) for s in plan.segments
    ]


def test_coverage_dummies_first_segment_only(rng):
    _, coo, plan = _bucketed(rng)
    counts = _dummy_counts(plan)
    assert len(counts) >= 2, "want a real multi-segment ladder"
    assert all(c == 0 for c in counts[1:]), counts
    # and validate_plan accepts the coverage-free ladder
    rep = validate_plan(plan, coo=coo)
    assert rep.ok, rep


def test_chain_bit_identical_to_per_segment_sum(rng):
    _, _, plan = _bucketed(rng)
    z = jnp.asarray(rng.integers(-4, 5, (128, 24)).astype(np.float32))
    chained = np.asarray(
        kops.scv_spmm_plan(plan, z, interpret=True, feature_block=8)
    )
    # per-segment-sum baseline: zero-init every segment independently, add
    summed = np.zeros_like(chained)
    for seg in plan.segments:
        summed += np.asarray(kref.scv_spmm_reference_plan(seg, z))
    np.testing.assert_array_equal(chained, summed)


def test_init_zeros_matches_and_needs_no_coverage(rng):
    _, _, plan = _bucketed(rng)
    z = jnp.asarray(rng.integers(-4, 5, (128, 16)).astype(np.float32))
    oracle = np.asarray(kref.scv_spmm_reference_plan(plan, z))
    out = np.asarray(
        kops.scv_spmm_plan(
            plan, z, interpret=True, feature_block=8, init="zeros"
        )
    )
    np.testing.assert_array_equal(out, oracle)
    with pytest.raises(ValueError):
        kops.scv_spmm_plan(plan, z, interpret=True, init="sideways")


def test_segments_longer_than_one_launch_chain_spans(rng, monkeypatch):
    """A segment past MAX_LAUNCH_TILES (the SMEM bound on prefetched tile
    arrays) runs as several accumulate-mode launches from zeros, and the
    result stays on the oracle for both the plan and the tiles entry."""
    from repro.serve.graph_engine import plan_launches

    _, _, plan = _bucketed(rng)
    z = jnp.asarray(rng.integers(-4, 5, (128, 16)).astype(np.float32))
    oracle = np.asarray(kref.scv_spmm_reference_plan(plan, z))
    one_launch_each = plan_launches(plan)
    monkeypatch.setattr(kops, "MAX_LAUNCH_TILES", 4)
    assert plan_launches(plan) == sum(
        -(-s.n_tiles // 4) for s in plan.segments
    ) > one_launch_each
    out = np.asarray(kops.scv_spmm_plan(plan, z, interpret=True, feature_block=8))
    np.testing.assert_array_equal(out, oracle)
    seg = plan.segments[0]
    single = np.asarray(kops.scv_spmm(
        seg.tile_row, seg.tile_col, seg.rows, seg.cols, seg.vals, z,
        tile=seg.tile, n_rows=seg.padded_shape[0],
        nnz_in_tile=seg.nnz_in_tile, feature_block=8, interpret=True,
    ))
    np.testing.assert_array_equal(
        single, np.asarray(kref.scv_spmm_reference_plan(seg, z))
    )


def test_chain_grads_match_reference(rng):
    _, _, plan = _bucketed(rng)
    z = jnp.asarray(rng.integers(-4, 5, (128, 16)).astype(np.float32))

    def loss_kernel(vals_list, z):
        segs = tuple(
            dataclasses.replace(s, vals=v)
            for s, v in zip(plan.segments, vals_list)
        )
        p = SCVBucketedPlan(segs)
        out = kops.scv_spmm_plan(p, z, interpret=True, feature_block=8)
        return jnp.sum(out * out)

    def loss_ref(vals_list, z):
        out = None
        for s, v in zip(plan.segments, vals_list):
            part = kref.scv_spmm_reference_plan(
                dataclasses.replace(s, vals=v), z
            )
            out = part if out is None else out + part
        return jnp.sum(out * out)

    vals_list = [s.vals for s in plan.segments]
    gv_k, gz_k = jax.grad(loss_kernel, argnums=(0, 1))(vals_list, z)
    gv_r, gz_r = jax.grad(loss_ref, argnums=(0, 1))(vals_list, z)
    np.testing.assert_allclose(np.asarray(gz_k), np.asarray(gz_r), atol=1e-4)
    for a, b in zip(gv_k, gv_r):
        if a.size:
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-4
            )


@pytest.mark.parametrize("kind", KINDS)
def test_chain_forward_and_grads_all_kinds(kind, rng):
    adj = gcn_normalize(powerlaw_graph(96, 700, seed=3))
    g = build_graph(adj, tile=16, bucket_caps=(8, 32))
    assert all(c == 0 for c in _dummy_counts(g.plan)[1:])
    x = jnp.asarray(rng.standard_normal((96, 12)).astype(np.float32))

    def run(backend):
        cfg = GNNConfig(
            name=f"t-{kind}", kind=kind, d_in=12, d_hidden=16,
            n_classes=4, n_layers=2, backend=backend,
        )
        params, _ = init_gnn(jax.random.PRNGKey(0), cfg)

        def loss(p):
            y = gnn_forward(p, cfg, g, x)
            return jnp.sum(y * y)

        return loss(params), jax.grad(loss)(params)

    y_k, g_k = run("pallas_interpret")
    y_r, g_r = run("jnp")
    np.testing.assert_allclose(float(y_k), float(y_r), rtol=2e-4)
    for a, b in zip(jax.tree_util.tree_leaves(g_k),
                    jax.tree_util.tree_leaves(g_r)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-3
        )


# ---------------------------------------------------------------------------
# 8 fake devices (subprocess: XLA flags must precede jax init): sharded
# spans chain with init="zeros" — no coverage, no per-segment sum tree
# ---------------------------------------------------------------------------
CHAIN_SHARD_SCRIPT = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import json
import numpy as np, jax, jax.numpy as jnp
from repro.core import (PlanExecutor, ShardingDecision, coo_to_scv_tiles,
                        plan_from_tiles_bucketed)
from repro.core.aggregate import aggregate_scv_plan
from repro.core.formats import COOMatrix
from repro.core.validate import validate_plan
from repro.simul.datasets import powerlaw_graph

res = {}
rng = np.random.default_rng(0)
adj = powerlaw_graph(700, 5000, seed=0)
adj = COOMatrix(adj.rows, adj.cols,
                rng.integers(-3, 4, adj.nnz).astype(np.float32), adj.shape)
tiles = coo_to_scv_tiles(adj, 32, cap=64)
bplan = plan_from_tiles_bucketed(tiles, caps=(8, 32, 64))
res["dummies"] = [int((np.asarray(s.nnz_in_tile) == 0).sum())
                  for s in bplan.segments]
z = jnp.asarray(rng.integers(-3, 4, (adj.shape[1], 16)).astype(np.float32))
single = np.asarray(aggregate_scv_plan(bplan, z, backend="jnp"))

ex = PlanExecutor()
for dec in (ShardingDecision("tiles", 4, 1),
            ShardingDecision("features", 1, 2),
            ShardingDecision("2d", 2, 2)):
    sp = ex.prepare(bplan, decision=dec)
    res[f"valid_{dec.kind}"] = bool(validate_plan(sp, coo=adj).ok)
    for backend in ("jnp", "pallas_interpret"):
        out = np.asarray(aggregate_scv_plan(sp, z, backend=backend))
        res[f"bit_{dec.kind}_{backend}"] = bool((out == single).all())
print(json.dumps(res))
'''


def test_sharded_coverage_free_on_oracle():
    import json
    import subprocess
    import sys

    res = subprocess.run(
        [sys.executable, "-c", CHAIN_SHARD_SCRIPT], capture_output=True,
        text=True, cwd=".", timeout=600,
    )
    assert res.returncode == 0, res.stderr[-3000:]
    r = json.loads(res.stdout.strip().splitlines()[-1])
    assert all(c == 0 for c in r["dummies"][1:]), r
    for kind in ("tiles", "features", "2d"):
        assert r[f"valid_{kind}"], r
        assert r[f"bit_{kind}_jnp"], r
        assert r[f"bit_{kind}_pallas_interpret"], r
