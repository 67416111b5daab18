#!/usr/bin/env python3
"""Chip smoke test: GCN serving through the compiled SCV kernel on a TPU.

    python3 chip_smoke.py             # one chip: the serving path
    python3 chip_smoke.py --chips 4   # four chips: sharded placement only

One chip.  Builds a ``GraphServeEngine`` for the ``gcn-paper`` config at
its full widths (d_in 128, hidden 128, 40 classes, 2 layers, aggregation
by the compiled ``scv_spmm`` kernel) and serves, through ``submit()`` /
``run()`` and through the async ``start()`` / ``stop()`` loop:

* one full-graph request on an arxiv-shaped graph (paper Table I at scale
  1.0: 169,343 nodes, 1,166,243 edges plus self loops);
* waves of molecule-sized graphs (ogbg-molhiv: ~25 nodes, ~27 edges)
  batched into block-diagonal composites.

Every request must finish without error, and every output must match a
plain float64 numpy GCN that aggregates by ``np.add.at`` over the COO
edges (no SCV code).  The kernel's own aggregation is also checked alone
against the same reference.  ``--chips 4`` instead places the arxiv plan
over four chips under each sharding decision, compares each with the
one-chip kernel output, and checks that every device holds its share.

Without a TPU the script exits non-zero and prints no result line.  The
last line of stdout on success is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

#: Kernel aggregation Â·Z against the float64 reference, as a fraction of
#: max|reference|.  The kernel sums in f32 in tile order, so the error is
#: f32 reassociation (~sqrt(degree) * 2^-24 of a row's terms).  A one-hot
#: matmul that rounded Z or the edge values to bf16 would show ~2^-9.
AGG_RTOL = 1e-5
#: Whole GCN against the float64 reference, as a fraction of
#: max|reference|.  The combination matmuls h @ W run at the TPU's default
#: f32 matmul precision, which rounds operands to bf16 (2^-9 relative);
#: on a v5e the arxiv-shaped request lands at 4.3e-3 of the output scale.
#: A dropped edge moves its row by ~1/degree of the row's value, far above
#: 1e-2 (tests/test_chip_smoke.py checks one).
GCN_RTOL = 1e-2

MOL_NODES, MOL_EDGES = 25, 27  # ogbg-molhiv mean graph size
WAVE_GRAPHS = 16  # molecules per composite wave
N_WAVES = 3


class SmokeFailure(AssertionError):
    """A phase of the smoke test failed."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# workload
# ---------------------------------------------------------------------------
def arxiv_graph(seed: int = 0):
    """The arxiv-shaped graph of paper Table I at scale 1.0, GCN-normalized."""
    from repro.simul.datasets import TABLE_I, load

    return load("arxiv", max_edges=TABLE_I["arxiv"].edges, seed=seed).adj


def molecule_graphs(n_graphs: int, seed: int = 0):
    """ogbg-molhiv-sized graphs, GCN-normalized."""
    from repro.simul.datasets import gcn_normalize, powerlaw_graph

    return [
        gcn_normalize(powerlaw_graph(MOL_NODES, MOL_EDGES, seed=seed + i))
        for i in range(n_graphs)
    ]


def gcn_paper_config(backend: str = "pallas"):
    import dataclasses

    from repro.configs.gcn_paper import spec

    return dataclasses.replace(spec.config, backend=backend)


def build_engine(cfg, params, big_nodes: int, executor=None, **kw):
    """An engine whose limits admit the full graph as one padded bucket:
    the default ladder tops out at 4096 nodes, past which a graph pads to
    the next power of two (169,344 -> 262,144 rows), and its plan exceeds
    the default 256 MiB plan cache, so it would be rebuilt every time."""
    from repro.core.scv import DEFAULT_TILE
    from repro.serve.graph_engine import GraphEngineConfig, GraphServeEngine

    small = (256, 512, 1024, 2048, 4096)
    aligned = -(-big_nodes // DEFAULT_TILE) * DEFAULT_TILE
    buckets = small + ((aligned,) if aligned > small[-1] else ())
    ecfg = GraphEngineConfig(
        max_batch_graphs=WAVE_GRAPHS,
        max_batch_nodes=buckets[-1],
        node_buckets=buckets,
        cache_bytes=4 << 30,
        **kw,
    )
    return GraphServeEngine({cfg.name: (params, cfg)}, ecfg, executor=executor)


# ---------------------------------------------------------------------------
# the plain reference (no SCV code, no JAX)
# ---------------------------------------------------------------------------
def coo_aggregate_f64(adj, z, chunk: int = 1 << 17) -> np.ndarray:
    """out[r] += v * z[c] over the COO entries, in float64."""
    z = np.asarray(z, np.float64)
    out = np.zeros((adj.shape[0], z.shape[1]), np.float64)
    vals = np.asarray(adj.vals, np.float64)
    for s in range(0, adj.nnz, chunk):
        sl = slice(s, s + chunk)
        np.add.at(out, adj.rows[sl], vals[sl, None] * z[adj.cols[sl]])
    return out


def reference_gcn(params, adj, x) -> np.ndarray:
    """The gcn forward of ``models.gnn`` in float64 numpy."""
    h = np.asarray(x, np.float64)
    n_layers = len(params)
    for i in range(n_layers):
        h = coo_aggregate_f64(adj, h @ np.asarray(params[f"layer{i}"]["w"], np.float64))
        if i + 1 < n_layers:
            h = np.maximum(h, 0.0)
    return h


def compare(what: str, out, ref, rtol: float) -> float:
    """max|out - ref| / max|ref|, checked against ``rtol``."""
    out = np.asarray(out)
    check(out.shape == ref.shape, f"{what}: shape {out.shape} != {ref.shape}")
    check(bool(np.isfinite(out).all()), f"{what}: non-finite output")
    scale = float(np.abs(ref).max()) or 1.0
    err = float(np.abs(out.astype(np.float64) - ref).max()) / scale
    check(err <= rtol, f"{what}: error {err:.3e} of max|ref| > {rtol:.0e}")
    return err


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def kernel_exactness(adj, backend: str, seed: int = 0, width: int = 128) -> float:
    """The kernel's aggregation Â·Z alone against the float64 reference."""
    import jax
    import jax.numpy as jnp

    from repro.core.aggregate import aggregate_scv_plan
    from repro.core.scv import DEFAULT_LADDER
    from repro.models.gnn import build_graph

    plan = build_graph(adj, bucket_caps=DEFAULT_LADDER, with_edges=False).plan
    z = np.random.default_rng(seed).standard_normal((adj.shape[0], width))
    z = z.astype(np.float32)
    agg = jax.jit(aggregate_scv_plan, static_argnames=("backend",))
    out = agg(plan, jnp.asarray(z), backend=backend)
    return compare("kernel aggregation", out, coo_aggregate_f64(adj, z), AGG_RTOL)


def check_served(engine, reqs) -> None:
    """Every request ended done, with no error; nothing failed, was shed
    or was rejected."""
    for r in reqs:
        check(r.error is None, f"request {r.rid} failed: {r.error}")
        check(r.done, f"request {r.rid} never finished")
    m = engine.metrics()
    for key in ("failed", "shed", "rejected"):
        check(m[key] == 0, f"engine reports {m[key]} {key} request(s)")


def serve_sync(engine, reqs) -> float:
    """submit() + run(); returns the wall seconds of the run."""
    for r in reqs:
        engine.submit(r)
    t0 = time.perf_counter()
    engine.run()
    dt = time.perf_counter() - t0
    check_served(engine, reqs)
    return dt


def serve_async(engine, reqs, timeout_s: float = 600.0) -> float:
    """The continuous-batching loop: start(), submit, wait, stop()."""
    t0 = time.perf_counter()
    engine.start()
    try:
        for r in reqs:
            engine.submit(r)
        for r in reqs:
            r.event.wait(timeout_s)
    finally:
        engine.stop(timeout=timeout_s)
    dt = time.perf_counter() - t0
    check_served(engine, reqs)
    return dt


def compile_wave(engine, wave, backend: str) -> float:
    """Compile the forward a wave runs (the jit cache keeps it); returns
    the compile seconds.  On the chip the program must hold the kernel."""
    t0 = time.perf_counter()
    compiled = engine.lower(wave).compile()
    dt = time.perf_counter() - t0
    if backend == "pallas":
        check("tpu_custom_call" in compiled.as_text(),
              "compiled forward holds no tpu_custom_call: the kernel did not run")
    return dt


def run_one_chip(cfg, big_adj, mol_adjs, seed: int = 0) -> dict:
    """The serving path on one device; returns its readings."""
    import jax

    from repro.models.gnn import init_gnn
    from repro.serve.graph_engine import GraphRequest

    readings = {"kernel_rel_err": kernel_exactness(big_adj, cfg.backend, seed)}
    params, _ = init_gnn(jax.random.PRNGKey(seed), cfg)
    rng = np.random.default_rng(seed)
    engine = build_engine(cfg, params, big_adj.shape[0])
    rid = iter(range(1 << 30))

    def request(adj, x):
        return GraphRequest(rid=next(rid), adj=adj, x=x, model=cfg.name)

    # full graph: compile, first serve, warm repeat (plan and program cached)
    x_big = rng.standard_normal((big_adj.shape[0], cfg.d_in)).astype(np.float32)
    ref_big = reference_gcn(params, big_adj, x_big)
    first = request(big_adj, x_big)
    readings["full_compile_s"] = compile_wave(engine, [first], cfg.backend)
    readings["full_first_serve_s"] = serve_sync(engine, [first])
    warm = request(big_adj, x_big)
    readings["full_warm_serve_s"] = serve_sync(engine, [warm])
    errs = [compare(f"full graph request {r.rid}", r.out, ref_big, GCN_RTOL)
            for r in (first, warm)]

    # molecule waves through submit()/run(); the warm reading repeats
    # the first wave (same composite plan and program)
    xs = [rng.standard_normal((a.shape[0], cfg.d_in)).astype(np.float32)
          for a in mol_adjs]
    refs = [reference_gcn(params, a, x) for a, x in zip(mol_adjs, xs)]
    mols = [request(a, x) for a, x in zip(mol_adjs, xs)]
    waves = [mols[i:i + WAVE_GRAPHS] for i in range(0, len(mols), WAVE_GRAPHS)]
    readings["wave_compile_s"] = compile_wave(engine, waves[0], cfg.backend)
    readings["wave_first_serve_s"] = [serve_sync(engine, w) for w in waves][0]
    repeat = [request(r.adj, r.x) for r in waves[0]]
    readings["wave_warm_serve_s"] = serve_sync(engine, repeat)
    errs += [compare(f"molecule request {r.rid}", r.out, ref, GCN_RTOL)
             for r, ref in zip(mols + repeat, refs + refs)]

    # everything again through the async scheduler loop
    again = [request(big_adj, x_big)] + [request(a, x) for a, x in zip(mol_adjs, xs)]
    readings["async_serve_s"] = serve_async(engine, again)
    errs += [compare(f"async request {r.rid}", r.out, ref, GCN_RTOL)
             for r, ref in zip(again, [ref_big] + refs)]

    m = engine.metrics()
    check(m["launches"] > 0, "engine counted no kernel launches")
    n_served = 2 + len(mols) + len(repeat) + len(again)
    check(m["completed"] == n_served,
          f"engine completed {m['completed']} of {n_served} requests")
    readings.update(gcn_rel_err=max(errs), launches=m["launches"],
                    waves=m["waves"], completed=m["completed"])
    return readings


def plan_bytes_by_device(plan) -> dict:
    """Bytes of plan leaves each device holds."""
    import jax

    held: dict = {}
    for leaf in jax.tree.leaves(plan):
        for shard in leaf.addressable_shards:
            held[shard.device] = held.get(shard.device, 0) + shard.data.nbytes
    return held


def run_four_chips(cfg, big_adj, devices, seed: int = 0) -> dict:
    """Sharded placement over four devices, compared with one device."""
    import jax
    import jax.numpy as jnp

    from repro.core.aggregate import aggregate_scv_plan
    from repro.core.exec import PlanExecutor, ShardingDecision
    from repro.core.scv import DEFAULT_LADDER
    from repro.models.gnn import build_graph, init_gnn
    from repro.serve.graph_engine import GraphRequest

    check(len(devices) == 4, f"need 4 devices, have {len(devices)}")
    plan = build_graph(big_adj, bucket_caps=DEFAULT_LADDER, with_edges=False).plan
    rng = np.random.default_rng(seed)
    z = rng.standard_normal((big_adj.shape[0], cfg.d_hidden)).astype(np.float32)
    agg = jax.jit(aggregate_scv_plan, static_argnames=("backend",))
    single = np.asarray(agg(plan, jax.device_put(z, devices[0]), backend=cfg.backend))
    compare("one-device kernel aggregation", single,
            coo_aggregate_f64(big_adj, z), AGG_RTOL)
    total = sum(leaf.nbytes for leaf in jax.tree.leaves(plan))

    executor = PlanExecutor(devices=tuple(devices), backend=cfg.backend)
    readings = {}
    for decision in (ShardingDecision("tiles", 4, 1),
                     ShardingDecision("features", 1, 4),
                     ShardingDecision("2d", 2, 2)):
        name = decision.signature
        placed = executor.prepare(plan, decision=decision)
        out = agg(placed, jnp.asarray(z), backend=cfg.backend)
        check(out.sharding.device_set == set(devices),
              f"{name}: output lives on {out.sharding.device_set}")
        if decision.feature_parts > 1:
            check(not out.sharding.is_fully_replicated,
                  f"{name}: feature-sharded output is replicated")
        # each device holds its own span of the placed plan (spans are
        # padded to one width, so the placed plan is a little larger)
        held = plan_bytes_by_device(placed)
        placed_total = sum(leaf.nbytes for leaf in jax.tree.leaves(placed))
        share = placed_total // decision.tile_parts
        check(set(held) == set(devices), f"{name}: plan held by {set(held)}")
        check(all(b == share for b in held.values()),
              f"{name}: devices hold {held}, not {share} bytes each")
        if decision.tile_parts > 1:
            check(share < total, f"{name}: each device holds the whole plan")
        readings[name] = {
            "rel_err_vs_one_device": compare(f"{name} placement", out, single, AGG_RTOL),
            "plan_bytes_per_device": share,
            "plan_bytes_one_device": total,
        }

    # the engine routes an over-threshold graph through the executor
    params, _ = init_gnn(jax.random.PRNGKey(seed), cfg)
    engine = build_engine(cfg, params, big_adj.shape[0], executor=executor,
                          shard_nodes_threshold=1024)
    x = rng.standard_normal((big_adj.shape[0], cfg.d_in)).astype(np.float32)
    req = GraphRequest(rid=0, adj=big_adj, x=x, model=cfg.name)
    serve_sync(engine, [req])
    check(engine.metrics()["sharded_batches"] == 1, "engine did not shard the graph")
    readings["engine_sharded_gcn_rel_err"] = compare(
        "sharded engine request", req.out, reference_gcn(params, big_adj, x), GCN_RTOL
    )
    return readings


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.graph_serve import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); refusing to "
              "run on another backend", file=sys.stderr)
        return 1
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device: {device}  compile cache: {cache_dir}", flush=True)

    cfg = gcn_paper_config("pallas")
    t0 = time.perf_counter()
    big = arxiv_graph(args.seed)
    print(f"arxiv-shaped graph: {big.shape[0]} nodes, {big.nnz} entries "
          f"(host build {time.perf_counter() - t0:.1f}s)", flush=True)
    try:
        if args.chips == 4:
            readings = run_four_chips(cfg, big, jax.devices()[:4], args.seed)
        else:
            mols = molecule_graphs(N_WAVES * WAVE_GRAPHS, args.seed)
            readings = run_one_chip(cfg, big, mols, args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    for key, val in readings.items():
        print(f"chip reading ({dev.device_kind}): {key} = {val}", flush=True)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
