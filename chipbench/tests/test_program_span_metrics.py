"""The readers of the program's spans (``repro.spans``), on CPU rehearsals of a
full-graph and a screening cell: what they read, and whether the spans agree
with the engine's counters and with the window."""
import time
from types import SimpleNamespace

import pytest

import tinycells as T
import harness
from repro import spans

STAGES = ("serve.form", "serve.plan", "serve.features", "serve.dispatch",
          "serve.device_wait", "serve.fetch", "serve.split")
READERS = {
    "full_graph": ["engine.host_ms.full"],
    "screen": ["plan.member_build_ms.screen", "plan.to_device_ms.screen",
               "plan.assemble_ms.screen"],
}


@pytest.fixture(scope="module", params=["full_graph", "screen"])
def rehearsal(request):
    """One rehearsal through ``harness.execute`` with the readers' names,
    its span log kept apart from the process's, and its workload and
    window kept for the test."""
    kind = request.param
    if kind == "full_graph":
        cfg, traffic, e2e = T.config(), T.full_graph_traffic(), "fullgraph_ms"
    else:
        cfg, traffic, e2e = T.config(molecules=True), T.stream_traffic(), "graphs_per_s"
    bench = harness.benchmark()
    every = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    specs = [every[n] for n in ["setup_s", e2e] + READERS[kind]]
    kept = []

    class Kept(harness.Workload):
        def window(self):
            win = super().window()
            kept.append((self, win))
            return win

    log = spans.SpanLog()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(spans, "LOG", log)
        mp.setattr(harness, "Workload", Kept)
        cell = {"name": "tiny.cell", "config": cfg["name"], "traffic": "tiny", "chips": 1}
        line = harness.execute(cell, cfg, traffic, specs, seed=11, seconds=0.6, trace=False,
                               devices=[T.CpuDevice()], peak=T.PEAK, t_start=time.monotonic(),
                               backend=T.BACKEND)
    (work, win), = kept
    recs = log.window(round(win.t_open * 1e9), round(win.t_end * 1e9))
    return SimpleNamespace(kind=kind, line=line, work=work, win=win, log=log, recs=recs)


def test_the_readers_return_positive_numbers(rehearsal):
    assert rehearsal.log.dropped == 0 and rehearsal.recs
    m = rehearsal.line["metrics"]
    for name in READERS[rehearsal.kind]:
        assert m[name]["value"] > 0, (name, m)


def test_the_verdict_is_still_correct(rehearsal):
    assert rehearsal.line["correct"], rehearsal.line["checks"]


def _builds(recs):
    return sum(r.name in ("serve.plan.build", "serve.plan.assemble") for r in recs)


def test_every_plan_cache_miss_has_one_build_span(rehearsal):
    """Each miss builds a member plan (``serve.plan.build``) or assembles a
    composite (``serve.plan.assemble``).  Over the engine's life the counts
    agree exactly; in the window the counter's delta may also hold misses
    of a wave whose builds start after the window's last completion, when
    the client reads the counters."""
    whole = rehearsal.work.engine.metrics()["plan_cache_misses"]
    assert _builds(rehearsal.log.records()) == whole > 0
    win = rehearsal.win
    delta = win.counters_end["plan_cache_misses"] - win.counters_open["plan_cache_misses"]
    if rehearsal.kind == "full_graph":
        # one outstanding: nothing is built while the client reads the counters
        assert _builds(rehearsal.recs) == delta == 0  # the graph's plan is cached
    else:
        assert 0 < _builds(rehearsal.recs) <= delta


def test_host_and_device_wait_fit_in_the_window(rehearsal):
    """Top-level spans of one thread never overlap, so what they cover of
    the window is at most the window.  One outstanding full graph puts the
    client's ``serve.submit`` in series with the scheduler's stages too."""
    win = rehearsal.win
    lo, hi = round(win.t_open * 1e9), round(win.t_end * 1e9)
    names = set(STAGES) | ({"serve.submit"} if rehearsal.kind == "full_graph" else set())
    covered = sum(max(0, min(r.end_ns, hi) - max(r.start_ns, lo)) for r in rehearsal.recs
                  if r.parent is None and r.name in names)
    done = len(win.completed())
    assert done and covered / done <= (hi - lo) / done


def test_each_wave_runs_its_stages_in_order(rehearsal):
    waves: dict = {}
    for r in rehearsal.recs:
        if r.parent is None and r.name in STAGES:
            waves.setdefault(r.ids["wave"], []).append(r)
    whole = [sorted(w, key=lambda r: r.start_ns) for w in waves.values() if len(w) == len(STAGES)]
    assert whole
    for w in whole:
        assert tuple(r.name for r in w) == STAGES
        assert all(a.end_ns <= b.start_ns for a, b in zip(w, w[1:]))


def _synthetic_run(monkeypatch, recs):
    """A run whose window, 0 to 1,000 ns, holds the spans ``recs``
    (name, start, end, parent sid, sid), and nothing else."""
    log = spans.SpanLog()
    for name, s, e, parent, sid in recs:
        log.add(spans.Span(name, s, e, parent, {}, sid))
    monkeypatch.setattr(spans, "LOG", log)
    return SimpleNamespace(window=SimpleNamespace(t_open=0.0, t_end=1e-6))


@pytest.mark.parametrize("puts,per_build_ns", [
    # every member build puts its own leaves
    ([("serve.plan.to_device", 12, 42, 1, 11), ("serve.plan.to_device", 110, 130, 2, 12)], 25),
    # members stay on the host; the assembly puts the composite once
    ([("serve.plan.to_device", 320, 380, 3, 13)], 30),
    # both: the assembly's put counts beside the members'
    ([("serve.plan.to_device", 12, 42, 1, 11), ("serve.plan.to_device", 320, 380, 3, 13)], 45),
], ids=["member_puts", "one_assembly_put", "both"])
def test_plan_puts_count_under_builds_and_assemblies(monkeypatch, puts, per_build_ns):
    """``plan.to_device_ms.screen`` sums every plan put in the window, under
    a member build or an assembly, over the member builds."""
    plan = [("serve.plan.build", 10, 50, None, 1), ("serve.plan.build", 100, 150, None, 2),
            ("serve.plan.assemble", 300, 400, None, 3)]
    run = _synthetic_run(monkeypatch, plan + puts)
    got = harness.metric_reader("plan.to_device_ms.screen").read(run)
    assert got == pytest.approx(per_build_ns / 1e6)


def test_plan_puts_without_member_builds_read_nothing(monkeypatch):
    run = _synthetic_run(monkeypatch, [("serve.plan.assemble", 300, 400, None, 3),
                                       ("serve.plan.to_device", 320, 380, 3, 13)])
    assert harness.metric_reader("plan.to_device_ms.screen").read(run) is None
