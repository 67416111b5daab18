"""The serving path's span log (repro.spans)."""
import glob
import os
import sys
import threading

import jax
import numpy as np
import pytest

from repro import spans
from repro.models.gnn import GNNConfig, init_gnn
from repro.serve.graph_engine import GraphEngineConfig, GraphRequest, GraphServeEngine
from repro.simul.datasets import gcn_normalize, powerlaw_graph
from repro.spans import Span, SpanLog, span


@pytest.fixture
def log(monkeypatch):
    """A fresh process log for the test."""
    fresh = SpanLog()
    monkeypatch.setattr(spans, "LOG", fresh)
    return fresh


def _rec(name, s, e, parent=None, sid=0):
    return Span(name, s, e, parent, {}, sid)


def test_parent_links_and_nesting(log):
    with span("a", wave=3) as ids:
        with span("b"):
            with span("c", rid=5):
                pass
        with span("d"):
            pass
        ids["rids"] = (1, 2)
    by = {r.name: r for r in log.records()}
    assert [r.name for r in log.records()] == ["c", "b", "d", "a"]  # order of closing
    assert by["a"].parent is None
    assert by["b"].parent == by["a"].sid and by["d"].parent == by["a"].sid
    assert by["c"].parent == by["b"].sid
    assert by["a"].ids == {"wave": 3, "rids": (1, 2)} and by["c"].ids == {"rid": 5}
    a, b, c = by["a"], by["b"], by["c"]
    assert a.start_ns <= b.start_ns <= c.start_ns <= c.end_ns <= b.end_ns <= a.end_ns
    assert by["d"].start_ns >= b.end_ns
    assert len({r.sid for r in log.records()}) == 4


def test_a_span_closes_when_its_body_raises(log):
    with pytest.raises(ValueError):
        with span("outer"):
            with span("inner"):
                raise ValueError("x")
    with span("after"):
        pass
    by = {r.name: r for r in log.records()}
    assert by["inner"].parent == by["outer"].sid
    assert by["after"].parent is None  # the stack unwound


def test_two_threads_keep_separate_stacks(log):
    opened, release = threading.Event(), threading.Event()

    def first():
        with span("t1.outer"):
            opened.set()
            release.wait(10)
            with span("t1.inner"):
                pass

    t = threading.Thread(target=first)
    t.start()
    assert opened.wait(10)
    with span("t2.outer"):
        with span("t2.inner"):
            pass
    release.set()
    t.join(10)
    by = {r.name: r for r in log.records()}
    assert by["t2.outer"].parent is None  # t1.outer is open, on another thread
    assert by["t2.inner"].parent == by["t2.outer"].sid
    assert by["t1.inner"].parent == by["t1.outer"].sid


def test_the_ring_is_bounded_and_counts_what_it_drops(log):
    ring = SpanLog(capacity=4)
    for i in range(6):
        ring.add(_rec(f"s{i}", 10 * i, 10 * i + 5, sid=i))
    assert [r.name for r in ring.records()] == ["s2", "s3", "s4", "s5"]
    assert ring.dropped == 2
    assert log.dropped == 0


def test_threads_recording_at_once_lose_no_count(monkeypatch):
    """More threads than cores, switching often: every span is either in
    the ring or counted as dropped, and each thread's parents are its own."""
    log = SpanLog(capacity=500)
    monkeypatch.setattr(spans, "LOG", log)
    n_threads, per_thread = 2 * (os.cpu_count() or 2), 200
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work(k):
            for _ in range(per_thread // 2):
                with span("outer", thread=k):
                    with span("inner", thread=k):
                        pass

        threads = [threading.Thread(target=work, args=(k,)) for k in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    recs = log.records()
    assert len(recs) == 500 and log.dropped == n_threads * per_thread - 500
    assert len({r.sid for r in recs}) == 500
    outer = {r.sid: r.ids["thread"] for r in recs if r.name == "outer"}
    for r in recs:
        if r.name == "inner" and r.parent in outer:
            assert outer[r.parent] == r.ids["thread"]
        if r.name == "outer":
            assert r.parent is None


def test_self_ns_subtracts_the_children():
    recs = [
        _rec("parent", 0, 100, sid=1),
        _rec("child", 10, 30, parent=1, sid=2),
        _rec("child", 20, 40, parent=1, sid=3),  # overlaps its sibling
        _rec("child", 90, 120, parent=1, sid=4),  # runs past its parent
        _rec("grandchild", 12, 18, parent=2, sid=5),
        _rec("other", 0, 50, sid=6),
    ]
    own = spans.self_ns(recs)
    assert own[1] == 100 - 30 - 10
    assert own[2] == 20 - 6
    assert own[3] == 20 and own[5] == 6 and own[6] == 50


def test_window_clips_and_refuses_what_dropped_records_reach_into():
    ring = SpanLog(capacity=3)
    for i, (s, e) in enumerate([(0, 10), (15, 40), (30, 35), (50, 60)]):
        ring.add(_rec(f"s{i}", s, e, sid=i))
    # s0 (0-10) was dropped
    assert ring.dropped == 1
    assert [r.name for r in ring.window(11, 100)] == ["s1", "s2", "s3"]
    assert [r.name for r in ring.window(20, 55)] == ["s2", "s3"]  # starts inside only
    assert ring.window(61, 100) == []
    assert ring.window(10, 100) is None  # the dropped s0 ends at 10
    assert ring.window(0, 5) is None
    ring.add(_rec("s4", 70, 80, sid=4))  # drops s1 (15-40)
    assert ring.window(20, 100) is None
    assert [r.name for r in ring.window(41, 100)] == ["s3", "s4"]


def _tiny_engine():
    cfg = GNNConfig(name="gcn", kind="gcn", d_in=8, d_hidden=8, n_classes=4)
    params, _ = init_gnn(jax.random.PRNGKey(0), cfg)
    return GraphServeEngine({"gcn": (params, cfg)}, GraphEngineConfig(tile=64, cap=64))


def _host_events(log_dir):
    """(name, start_ns, end_ns) of every event on the trace's host planes."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    out.append((ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns)))
    return out


def test_a_served_wave_leaves_its_spans_on_the_profilers_host_plane(log, tmp_path):
    eng = _tiny_engine()
    rng = np.random.default_rng(0)
    adjs = [gcn_normalize(powerlaw_graph(n, 3, seed=i)) for i, n in enumerate([60, 90])]
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        with jax.profiler.TraceAnnotation("test.wave"):
            for i, a in enumerate(adjs):
                x = rng.standard_normal((a.shape[0], 8)).astype(np.float32)
                eng.submit(GraphRequest(rid=i, adj=a, x=x, model="gcn"))
            done = eng.run()
    assert len(done) == 2
    ran = {r.name for r in log.records()}
    assert {"serve.submit", "serve.form", "serve.plan", "serve.plan.key",
            "serve.plan.build", "serve.plan.to_device", "serve.plan.assemble",
            "serve.features", "serve.dispatch", "serve.device_wait", "serve.fetch",
            "serve.split"} <= ran
    events = _host_events(tmp_path)
    (lo, hi), = [(s, e) for n, s, e in events if n == "test.wave"]
    inside = {n for n, s, e in events if lo <= s and e <= hi}
    assert ran <= inside
    # the ids tie the wave's stages together
    wave = {r.ids.get("wave") for r in log.records()
            if r.name in ("serve.form", "serve.plan", "serve.dispatch", "serve.split")}
    assert wave == {done[0].wave} and done[0].wave == done[1].wave
