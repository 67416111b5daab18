"""CPU rehearsals of the harness: traffic generators, the closed loop, metrics and
the correctness check, at tiny sizes with the kernel in interpret mode."""
import threading
import time

import numpy as np
import pytest

import tinycells as T
import graphs
import harness
from repro.core.formats import COOMatrix
from repro.serve import graph_engine


# ---------------------------------------------------------------------------
# generators
# ---------------------------------------------------------------------------
def test_molecule_sizes_are_the_same_multiset_for_every_seed():
    spec = T.MOLECULES
    a = graphs.stratified_sizes(5000, spec, np.random.default_rng(1))
    b = graphs.stratified_sizes(5000, spec, np.random.default_rng(2))
    assert np.array_equal(np.sort(a), np.sort(b)) and not np.array_equal(a, b)
    assert a.mean() == pytest.approx(spec["mean_atoms"], rel=0.03)
    assert a.min() >= spec["min_atoms"] and a.max() <= spec["max_atoms"]


def _tile_histogram(g, tile=64):
    t = (g.rows // tile).astype(np.int64) * -(-g.n // tile) + g.cols // tile
    counts = np.bincount(t)
    return np.sort(counts[counts > 0])


def test_a_blocked_table_graph_has_the_same_tiles_for_every_seed():
    spec = {"kind": "table_i", "name": "tiny", "nodes": 1000, "edges": 6000, "scale": 1.0, "block": 64}
    a, b = graphs.table_graph(spec, 1), graphs.table_graph(spec, 2)
    assert np.array_equal(_tile_histogram(a), _tile_histogram(b))
    assert not np.array_equal(a.rows, b.rows)
    # a renaming of one graph: the same entries, and each node keeps its degree
    assert sorted(a.vals.tolist()) == sorted(b.vals.tolist())
    assert np.array_equal(np.sort(np.bincount(a.rows)), np.sort(np.bincount(b.rows)))
    assert len(set(zip(a.rows.tolist(), a.cols.tolist()))) == len(set(zip(b.rows.tolist(), b.cols.tolist())))
    # without the key, the seed draws another graph, tiles and all
    del spec["block"]
    c, d = graphs.table_graph(spec, 1), graphs.table_graph(spec, 2)
    assert not np.array_equal(_tile_histogram(c), _tile_histogram(d))


def test_molecules_are_undirected_bounded_and_normalised():
    spec = T.MOLECULES
    rng = np.random.default_rng(4)
    mols = graphs.molecules(graphs.stratified_sizes(300, spec, rng), spec, rng)
    assert mols.bonds().mean() == pytest.approx(spec["mean_bonds"], rel=0.1)
    assert mols.degree.max() <= spec["max_degree"]
    for i in (0, 7, 123):
        g = mols.graph(i)
        a = np.zeros((g.n, g.n))
        a[g.rows, g.cols] = g.vals
        assert np.allclose(a, a.T) and np.all(np.diag(a) > 0)
        assert len(set(zip(g.rows.tolist(), g.cols.tolist()))) == g.nnz
        # connected: a random tree plus ring closures
        reach = np.linalg.matrix_power(a > 0, g.n)
        assert np.all(reach[0] > 0)


# ---------------------------------------------------------------------------
# the closed loop and end-to-end metrics
# ---------------------------------------------------------------------------
class FakeEngine:
    """Completes each request after a fixed delay on its own thread and
    counts how many are outstanding at once."""

    def __init__(self, delay: float):
        self.delay, self.outstanding, self.most = delay, 0, 0
        self.lock = threading.Lock()

    def submit(self, req, block=True):
        with self.lock:
            self.outstanding += 1
            self.most = max(self.most, self.outstanding)

        def finish():
            time.sleep(self.delay)
            with self.lock:
                self.outstanding -= 1
            req.out, req.done = np.zeros((1, 1)), True
            req.event.set()

        threading.Thread(target=finish, daemon=True).start()

    def metrics(self):
        return {k: 0 for k in ("completed", "waves", "launches", "plan_cache_hits",
                               "plan_cache_misses", "plan_build_seconds", "failed", "shed",
                               "rejected")}


def _item(i):
    adj = COOMatrix(np.zeros(1, np.int32), np.zeros(1, np.int32), np.ones(1, np.float32), (1, 1))
    return harness.Item(adj, np.zeros((1, 1), np.float32), i)


@pytest.mark.parametrize("concurrency", [1, 4])
def test_closed_loop_keeps_its_concurrency(concurrency):
    eng = FakeEngine(delay=0.01)
    client = harness.Client(eng, "default")
    win = harness.closed_loop(client, _item, concurrency, 0.3)
    assert eng.most == concurrency
    done = win.completed()
    assert win.t_end >= win.t_close and all(r.done <= win.t_end for r in done)
    # the window ends at the first completion at or after the close
    assert min(r.done for r in win.records if r.done and r.done >= win.t_close) == win.t_end
    assert len(done) / win.seconds == pytest.approx(concurrency / 0.01, rel=0.5)


# ---------------------------------------------------------------------------
# whole runs through the engine, interpret mode
# ---------------------------------------------------------------------------
def test_full_graph_cell_rehearsal():
    line = T.execute(T.config(), T.full_graph_traffic(), seconds=0.6)
    assert line["correct"], line["checks"]
    assert line["attempted"] >= 2 and line["failed"] == 0
    m = line["metrics"]
    assert m["fullgraph_ms"]["value"] > 0 and m["setup_s"]["value"] > 0
    assert line["checks"]["compared"]["value"] == line["attempted"]


def test_screen_cell_rehearsal():
    line = T.execute(T.config(molecules=True), T.stream_traffic(), seconds=0.5)
    assert line["correct"], line["checks"]
    assert line["attempted"] >= 4 and line["failed"] == 0
    assert line["metrics"]["graphs_per_s"]["value"] > 0


def test_screen_stream_sends_distinct_molecules():
    cfg, traffic = T.config(molecules=True), T.stream_traffic()
    work = harness.Workload(cfg, traffic, 6, 0.5, T.BACKEND)
    work.warm_up()
    win = work.window()
    work.stop()
    keys = [r.key for r in win.records]
    assert len(keys) == len(set(keys)) >= 8
    # the window starts past every molecule the warm-up sent
    assert min(keys) >= traffic["loop"]["warmup_requests"]
    assert harness.check(win, work)["correct"]


def test_dropped_edge_is_caught():
    """The engine serves the graph with one edge left out; the reference
    has it."""
    cfg, traffic = T.config(), T.full_graph_traffic()
    work = harness.Workload(cfg, traffic, 8, 0.3, T.BACKEND)
    a = work.source.adj
    keep = np.ones(a.nnz, bool)
    keep[np.argmax(a.rows != a.cols)] = False  # the first edge that is no self loop
    work.source.adj = COOMatrix(a.rows[keep], a.cols[keep], a.vals[keep], a.shape)
    work.warm_up()
    win = work.window()
    work.stop()
    verdict = harness.check(win, work)
    assert not verdict["correct"]
    assert verdict["checks"]["out_gap"]["value"] > cfg["limits"]["out_gap"]


SPLIT = graph_engine.split_outputs


def _altered(bg, out):
    """An answer altered where it is produced: one value of each output."""
    outs = SPLIT(bg, out)
    for o in outs:
        o[0, 0] += 1.0
    return outs


def _half_left_out(bg, out):
    """Half of each wave left out: its answers are not computed."""
    outs = SPLIT(bg, out)
    k = max(1, len(outs) // 2)
    return [np.zeros_like(o) if i < k else o for i, o in enumerate(outs)]


@pytest.mark.parametrize("fault", [_altered, _half_left_out], ids=["answer_altered", "half_left_out"])
@pytest.mark.parametrize("traffic", ["full_graph", "stream"])
def test_a_broken_timed_path_is_not_correct(monkeypatch, fault, traffic):
    monkeypatch.setattr(graph_engine, "split_outputs", fault)
    if traffic == "full_graph":
        line = T.execute(T.config(), T.full_graph_traffic(), seconds=0.3)
    else:
        line = T.execute(T.config(molecules=True), T.stream_traffic(), seconds=0.3)
    assert line["correct"] is False
    assert line["checks"]["out_gap"]["value"] > line["checks"]["out_gap"]["limit"]


@pytest.mark.parametrize("combination,limit", [("float32", 1e-4), ("bfloat16", 1e-3)])
def test_the_control_fails_the_limit_the_program_passes(combination, limit):
    """Each stage one step below its stated precision comes out not
    correct; the served answers and the exact (float64) answers come out
    correct, whether or not they round where the stated precision does."""
    import control

    cfg, traffic = T.config(combination=combination, limit=limit), T.full_graph_traffic()
    work = harness.Workload(cfg, traffic, 9, 0.3, T.BACKEND)
    work.warm_up()
    win = work.window()
    work.stop()
    r = control.readings(work, win)
    assert r["program"]["correct"] and r["exact"]["correct"]
    assert r["exact"]["out_gap"] == 0.0 and r["program"]["out_gap"] < limit / 10
    controls = [k for k in r if k.startswith(("combination_", "aggregation_"))]
    assert len(controls) == 2
    for name in controls:
        assert not r[name]["correct"] and r[name]["out_gap"] > 2 * limit, (name, r[name])
