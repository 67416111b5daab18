"""Autotuner subsystem (repro.tune): TunedConfig, histogram/ladder edge
cases, signature stability, slot-priced byte model, store round-trips,
search caching, and the serve engine's autotune integration."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.core.exec import PlanExecutor, placement_bytes
from repro.core.formats import COOMatrix
from repro.core.scv import (
    DEFAULT_CAP,
    DEFAULT_CHUNK,
    DEFAULT_LADDER,
    DEFAULT_TILE,
    MIN_BUCKET_CAP,
    MXU_VPU_RATIO,
    bucket_caps_for,
    coo_to_scv_tiles,
    launched_slots,
    plan_from_tiles_bucketed,
    tile_nnz_histogram,
)
from repro.models.gnn import build_graph
from repro.simul.datasets import gcn_normalize, powerlaw_graph
from repro.simul.machine import MachineConfig
from repro.tune import (
    Autotuner,
    TuneStore,
    TunedConfig,
    cache_key,
    histogram_signature,
    machine_fingerprint,
    plan_launched_slots,
    quantize_histogram,
)


def _empty_coo(n=128):
    z = np.zeros(0, np.int32)
    return COOMatrix(z, z.copy(), np.zeros(0, np.float32), (n, n))


# ---------------------------------------------------------------------------
# histogram / ladder edge cases (satellite 3)
# ---------------------------------------------------------------------------
def test_tile_nnz_histogram_empty_graph():
    counts = tile_nnz_histogram(_empty_coo(), 64)
    assert counts.size == 0
    assert bucket_caps_for(counts, 64) == (MIN_BUCKET_CAP,)


def test_bucket_caps_single_mega_tile_clamps_to_dense():
    # one fully dense 64x64 tile: 4096 entries == T^2, the maximum any
    # tile can hold — the ladder tops out at T^2, never above
    r, c = np.meshgrid(np.arange(64, dtype=np.int32),
                       np.arange(64, dtype=np.int32))
    adj = COOMatrix(r.ravel(), c.ravel(),
                    np.ones(64 * 64, np.float32), (64, 64))
    counts = tile_nnz_histogram(adj, 64)
    assert list(counts) == [4096]
    caps = bucket_caps_for(counts, 64)
    assert caps == (64, 256, 1024, 4096)
    # a hypothetical count above T^2 (can't arise from unique entries)
    # still clamps to the dense size
    assert bucket_caps_for(np.array([5000]), 64) == (64, 256, 1024, 4096)


def test_launched_slots_edge_cases():
    # empty histogram: only the coverage bound
    assert launched_slots(np.zeros(0, np.int64), 64, (8, 32)) == 0
    assert launched_slots(np.zeros(0, np.int64), 64, (8, 32), n_row_blocks=4) == 32
    # chain-split at the top cap: 70 entries at caps (8, 32) ->
    # 2 full 32-chunks + remainder 6 in the 8-cap bucket
    assert launched_slots(np.array([70]), 64, (8, 32)) == 32 + 32 + 8
    # exact-fit remainder lands in its own cap, not the next one up
    assert launched_slots(np.array([8]), 64, (8, 32)) == 8
    with pytest.raises(ValueError):
        launched_slots(np.array([1]), 64, ())


def test_launched_slots_brackets_built_plan():
    adj = powerlaw_graph(1 << 12, 40_000, seed=3)
    T = 64
    counts = tile_nnz_histogram(adj, T)
    caps = bucket_caps_for(counts, T)
    tiles = coo_to_scv_tiles(adj, T, cap=caps[-1])
    plan = plan_from_tiles_bucketed(tiles, caps=caps)
    built = plan_launched_slots(plan)
    lo = launched_slots(counts, T, caps)  # no coverage dummies
    hi = launched_slots(
        counts, T, caps, n_row_blocks=-(-adj.shape[0] // T)
    )  # every block row dummied — the upper bound
    assert lo <= built <= hi
    # tile slots (sans coverage) must match the split arithmetic exactly
    n_cov = built - lo
    assert 0 <= n_cov <= (-(-adj.shape[0] // T)) * caps[0]


# ---------------------------------------------------------------------------
# signature stability (satellite 3: cache key under ±1 perturbations)
# ---------------------------------------------------------------------------
def test_histogram_signature_stable_under_unit_perturbations():
    adj = powerlaw_graph(1 << 13, 120_000, seed=0)
    counts = tile_nnz_histogram(adj, DEFAULT_TILE)
    sig = histogram_signature(counts)
    for idx in (0, counts.size // 2, counts.size - 1):
        for delta in (-1, +1):
            pert = counts.copy()
            pert[idx] = max(1, pert[idx] + delta)
            assert histogram_signature(pert) == sig, (idx, delta)
    # dropping / adding one whole tile is also sub-quantum
    assert histogram_signature(counts[1:]) == sig
    assert histogram_signature(np.append(counts, counts[-1])) == sig


def test_histogram_signature_separates_regimes():
    sparse = tile_nnz_histogram(powerlaw_graph(1 << 13, 120_000, seed=0),
                                DEFAULT_TILE)
    dense = tile_nnz_histogram(
        gcn_normalize(powerlaw_graph(256, 30_000, seed=0)), DEFAULT_TILE
    )
    assert histogram_signature(sparse) != histogram_signature(dense)
    assert quantize_histogram(sparse, DEFAULT_TILE) != quantize_histogram(
        dense, DEFAULT_TILE
    )


def test_machine_fingerprint_tracks_config():
    base = machine_fingerprint(MachineConfig())
    assert machine_fingerprint(MachineConfig()) == base
    assert machine_fingerprint(MachineConfig(dram_gbps=2.0)) != base
    assert cache_key("abc", base) != cache_key("abd", base)


# ---------------------------------------------------------------------------
# TunedConfig
# ---------------------------------------------------------------------------
def test_tuned_config_defaults_mirror_core_constants():
    cfg = TunedConfig.default()
    assert (cfg.tile, cfg.chunk, cfg.cap) == (
        DEFAULT_TILE, DEFAULT_CHUNK, DEFAULT_CAP
    )
    assert cfg.bucket_caps == DEFAULT_LADDER
    assert cfg.dense_threshold_ratio == MXU_VPU_RATIO
    assert cfg.dense_tile_threshold() == int(
        DEFAULT_TILE * DEFAULT_TILE * MXU_VPU_RATIO
    )


def test_tuned_config_equality_ignores_source():
    a = TunedConfig(source="default")
    b = dataclasses.replace(a, source="simulated")
    assert a == b and hash(a) == hash(b)
    assert a != dataclasses.replace(a, tile=128)


def test_tuned_config_validation():
    with pytest.raises(ValueError):
        TunedConfig(tile=48)  # not a power of two
    with pytest.raises(ValueError):
        TunedConfig(bucket_caps=(32, 8))  # descending
    with pytest.raises(ValueError):
        TunedConfig(dense_threshold_ratio=0.0)
    assert TunedConfig(bucket_caps=()).cap_signature == DEFAULT_CAP
    assert TunedConfig().cap_signature == DEFAULT_LADDER


def test_tuned_config_json_roundtrip():
    cfg = TunedConfig(tile=128, chunk=64, bucket_caps=(16, 64, 256))
    assert TunedConfig.from_json(cfg.to_json()) == cfg


# ---------------------------------------------------------------------------
# slot-priced placement byte model (satellite 1)
# ---------------------------------------------------------------------------
def test_placement_bytes_n_slots_prices_launched_plan():
    nnz, slots, f = 10_000, 23_456, 64
    legacy = placement_bytes(nnz, f, 2, 1, n_rows=4096)
    slotted = placement_bytes(nnz, f, 2, 1, n_rows=4096, n_slots=slots)
    b = MachineConfig().bytes_per_elem
    assert legacy["plan"] == 3 * nnz * b / 2
    assert slotted["plan"] == 3 * slots * b / 2
    # only the plan term (and the totals through it) may move
    for k in ("z_slab", "out", "z_gather", "collective"):
        assert slotted[k] == legacy[k]
    assert slotted["resident"] - legacy["resident"] == pytest.approx(
        3 * (slots - nnz) * b / 2
    )


def test_executor_decide_uses_exact_plan_slots():
    adj = powerlaw_graph(1 << 12, 40_000, seed=1)
    g = build_graph(adj, config=TunedConfig.default())
    ex = PlanExecutor()
    dec = ex.decide(g.plan, 64)
    # single test device -> replicated; the point is the path runs and
    # prices the plan's real launched slots without touching device data
    assert dec.kind in ("replicated", "tiles", "features", "2d")
    assert plan_launched_slots(g.plan) == sum(
        int(s.n_tiles) * int(s.cap) for s in g.plan.segments
    )


# ---------------------------------------------------------------------------
# store
# ---------------------------------------------------------------------------
def test_store_roundtrip_on_disk(tmp_path):
    path = tmp_path / "tune.json"
    s1 = TuneStore(path)
    assert s1.get("k") is None
    cfg = TunedConfig(tile=128, bucket_caps=(16, 64), source="simulated")
    s1.put("k", cfg, meta={"note": 1})
    s2 = TuneStore(path)  # fresh process view
    got = s2.get("k")
    assert got == cfg
    assert s2.hits == 1 and s1.misses == 1


def test_store_corrupt_file_is_empty(tmp_path):
    path = tmp_path / "tune.json"
    path.write_text("{not json")
    s = TuneStore(path)
    assert len(s) == 0 and s.get("k") is None


# ---------------------------------------------------------------------------
# autotuner
# ---------------------------------------------------------------------------
def test_autotuner_search_then_cache_hit(tmp_path):
    adj = powerlaw_graph(1 << 12, 60_000, seed=0)
    store = TuneStore(tmp_path / "tune.json")
    tuner = Autotuner(store=store)
    cfg = tuner.tune(adj, n_features=16)
    assert tuner.searches == 1 and tuner.cache_hits == 0
    assert cfg.source == "simulated"
    assert len(tuner.last_result.candidates) > 3
    # same regime again: store hit, no re-search
    assert tuner.tune(adj, n_features=16) == cfg
    assert tuner.searches == 1 and tuner.cache_hits == 1
    assert tuner.last_result.cached
    # a fresh tuner sharing the on-disk store inherits the hit
    t2 = Autotuner(store=TuneStore(tmp_path / "tune.json"))
    assert t2.tune(adj, n_features=16) == cfg
    assert t2.searches == 0 and t2.cache_hits == 1


def test_autotuner_machine_change_is_stale(tmp_path):
    adj = powerlaw_graph(1 << 12, 60_000, seed=0)
    store = TuneStore(tmp_path / "tune.json")
    Autotuner(store=store).tune(adj, n_features=16)
    other = Autotuner(machine=MachineConfig(dram_gbps=4.0), store=store)
    other.tune(adj, n_features=16)
    assert other.searches == 1  # fingerprint miss -> fresh search
    assert len(store) == 2


def test_autotuner_empty_graph_returns_default():
    tuner = Autotuner()
    assert tuner.tune(_empty_coo()) == TunedConfig.default()
    assert tuner.searches == 0


# ---------------------------------------------------------------------------
# build_graph / plan_from_tiles_bucketed config threading
# ---------------------------------------------------------------------------
def test_build_graph_accepts_tuned_config():
    adj = gcn_normalize(powerlaw_graph(300, 2_000, seed=2))
    cfg = TunedConfig(tile=32, bucket_caps=(8, 32))
    g = build_graph(adj, config=cfg)
    assert g.plan.tile == 32
    assert tuple(s.cap for s in g.plan.segments) == (8, 32)
    # explicit layout args conflict with a config
    with pytest.raises(ValueError):
        build_graph(adj, tile=32, config=cfg)
    # empty ladder -> single-cap plan at config.cap
    g2 = build_graph(adj, config=TunedConfig(bucket_caps=(), cap=16))
    assert not hasattr(g2.plan, "segments") and g2.plan.cap == 16


def test_plan_from_tiles_bucketed_config():
    adj = powerlaw_graph(1 << 10, 8_000, seed=4)
    cfg = TunedConfig(tile=64, bucket_caps=(8, 32))
    tiles = coo_to_scv_tiles(adj, cfg.tile, cap=cfg.bucket_caps[-1])
    plan = plan_from_tiles_bucketed(tiles, config=cfg)
    assert tuple(s.cap for s in plan.segments) == (8, 32)
    with pytest.raises(ValueError):
        plan_from_tiles_bucketed(tiles, caps=(8, 32), config=cfg)


# ---------------------------------------------------------------------------
# serve engine integration
# ---------------------------------------------------------------------------
def _autotune_engine(tmp_path=None, **cfg_kw):
    from repro.models.gnn import GNNConfig, init_gnn
    from repro.serve.graph_engine import GraphEngineConfig, GraphServeEngine

    mcfg = GNNConfig(name="gcn", kind="gcn", d_in=8, d_hidden=8, n_classes=4)
    params, _ = init_gnn(jax.random.PRNGKey(0), mcfg)
    ecfg = GraphEngineConfig(**cfg_kw)
    return GraphServeEngine({"gcn": (params, mcfg)}, ecfg), params, mcfg


def test_engine_autotune_matches_default_outputs(rng):
    from repro.models.gnn import gnn_forward
    from repro.serve.graph_engine import GraphRequest

    adjs = [gcn_normalize(powerlaw_graph(n, 4 * n, seed=9 + i))
            for i, n in enumerate([90, 150])]
    xs = [rng.standard_normal((a.shape[0], 8)).astype(np.float32)
          for a in adjs]
    eng, params, mcfg = _autotune_engine(autotune=True)
    assert eng.tuner is not None
    for i, (a, x) in enumerate(zip(adjs, xs)):
        eng.submit(GraphRequest(rid=i, adj=a, x=x, model="gcn"))
    done = eng.run()
    assert len(done) == 2 and all(r.done for r in done)
    import jax.numpy as jnp

    for r in done:
        # the tuned layout must be numerically irrelevant
        ref = np.asarray(gnn_forward(
            params, mcfg, build_graph(r.adj), jnp.asarray(r.x)
        ))
        np.testing.assert_allclose(r.out, ref, atol=1e-5, rtol=1e-5)
    m = eng.metrics()
    assert m["autotune_enabled"] and m["autotune_searches"] >= 1
    assert m["resolved_configs"], "resolved configs must surface in metrics"


def test_engine_autotune_off_uses_fallback_literals():
    eng, _, _ = _autotune_engine()
    m = eng.metrics()
    assert not m["autotune_enabled"] and m["autotune_searches"] == 0
    assert eng._fallback_config.tile == DEFAULT_TILE
    assert eng._fallback_config.bucket_caps == DEFAULT_LADDER
