"""The trace reduction, the work counts and the refusals, on the CPU."""
import json
import os
import pathlib
import re
import subprocess
import sys

import pytest

import tinycells  # noqa: F401  (puts chipbench on sys.path)
import cost
import harness

tr = harness.trace_module()

REPO = pathlib.Path(__file__).resolve().parents[2]


def test_union_busy_and_gaps():
    ops = [("a", 0, 10), ("b", 5, 20), ("c", 30, 40), ("d", 50, 55)]
    assert tr.merged(ops) == [(0, 20), (30, 40), (50, 55)]
    assert tr.busy_ns(ops, 0, 60) == 35
    assert tr.busy_ns(ops, 8, 35) == 17  # clipped to the window
    assert tr.gaps(ops, 0, 60) == [(20, 30), (40, 50), (55, 60)]
    assert tr.gaps(ops, -5, 12) == [(-5, 0)]


def test_kernel_sum_and_top_ops():
    ops = [("%scv_spmm.1 = f32[8,128]{1,0} custom-call(s32[8]{0} %a)", 0, 10),
           ("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %b)", 10, 12),
           ("%scv_spmm.12 = f32[8,128]{1,0} custom-call(s32[8]{0} %c)", 12, 30),
           ("%copy-done.3 = f32[8]{0} copy-done(%d)", 40, 45),
           ("%slice.4 = f32[8,40]{1,0} slice(f32[8,128]{1,0} %scv_spmm.12)", 45, 46)]
    assert tr.kernel_ns(ops, "scv_spmm", 0, 100) == 28
    assert tr.kernel_ns(ops, "scv_spmm", 5, 20) == 5 + 8
    top = tr.top_ops(ops, 0, 100, k=2)
    assert top == [["scv_spmm", 28e-9], ["copy-done", 5e-9]]
    assert tr.op_kind("fusion") == "fusion"


def test_idle_gaps_are_labelled_by_host_spans():
    ops = [("op", 0, 10), ("op", 40, 50), ("op", 52, 60)]
    spans = [("chipbench.window", 0, 60), ("chipbench.submit", 10, 15),
             ("chipbench.wait", 15, 40), ("chipbench.sleep", 50, 52)]
    gaps = tr.labelled_gaps(ops, spans, 0, 60)
    assert gaps[0] == ["chipbench.wait", 30e-9]
    assert gaps[1] == ["chipbench.sleep", 2e-9]


def test_idle_gaps_are_labelled_by_the_innermost_program_span():
    """A gap inside ``chipbench.wait`` that holds ``serve.features`` is the
    features' gap; of nested program spans the innermost names it, for
    the longest part of the gap; a gap no program span reaches keeps the
    benchmark's label, and one no span reaches has none."""
    ops = [("op", 0, 10), ("op", 40, 50), ("op", 70, 80), ("op", 85, 90), ("op", 95, 100)]
    spans = [("chipbench.window", 0, 110), ("chipbench.wait", 10, 80),
             ("serve.features", 12, 38),
             ("serve.plan", 50, 70), ("serve.plan.build", 52, 65), ("serve.plan.to_device", 55, 58),
             ("chipbench.submit", 80, 90)]
    gaps = tr.labelled_gaps(ops, spans, 0, 110)
    assert gaps == [["serve.features", 30e-9], ["serve.plan.build", 20e-9],
                    ["no benchmark span", 10e-9], ["chipbench.submit", 5e-9],
                    ["no benchmark span", 5e-9]]
    # spans that hold the whole gap: the one that started last is innermost
    held = [("serve.form", 0, 60), ("serve.absorb", 5, 60), ("serve.fetch", 45, 48)]
    assert tr.labelled_gaps(ops, held, 0, 60)[0] == ["serve.absorb", 30e-9]
    # spans of two threads do not nest: a client's submit that starts
    # inside the scheduler's build does not hide the build
    threads = [("serve.plan", 10, 40, "sched"), ("serve.plan.build", 11, 40, "sched"),
               ("serve.submit", 12, 30, "client")]
    assert tr.labelled_gaps(ops, threads, 0, 60)[0] == ["serve.plan.build", 30e-9]


def test_reduce_averages_busy_over_devices():
    t = tr.Trace(
        device_ops={"/device:TPU:0": [("scv_spmm", 0, 50)],
                    "/device:TPU:1": [("scv_spmm", 0, 30), ("fusion", 60, 70)]},
        host_spans=[("chipbench.window", 0, 100), ("chipbench.wait", 0, 100)],
        planes=[],
    )
    r = tr.reduce(t)
    assert r.window_s == pytest.approx(100e-9)
    assert r.busy_s == pytest.approx(45e-9)
    assert r.kernel_s["scv_spmm"] == pytest.approx(80e-9)
    assert r.idle_gaps[0] == ["chipbench.wait", 50e-9]


def test_reduce_needs_window_and_device_ops():
    with pytest.raises(ValueError, match="window"):
        tr.reduce(tr.Trace({"/device:TPU:0": [("x", 0, 1)]}, [], []))
    with pytest.raises(ValueError, match="device operations"):
        tr.reduce(tr.Trace({}, [("chipbench.window", 0, 1)], []))


def test_recorded_cpu_trace_holds_the_benchmark_spans(tmp_path):
    """A trace recorded here has no device plane, but the loader finds
    the benchmark's and the program's (``serve.*``) host spans and the
    window in it, and drops other host events."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda a: (a @ a).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        with harness.span("chipbench.window"):
            for _ in range(3):
                with harness.span("chipbench.submit"), harness.span("serve.features"):
                    f(x).block_until_ready()
            with harness.span("other.span"):
                pass
    t = tr.load(str(tmp_path))
    names = {n for n, *_ in t.host_spans}
    assert names == {"chipbench.window", "chipbench.submit", "serve.features"}
    lo, hi = tr.window(t)
    assert hi > lo
    assert any(p[0].startswith("/host:") for p in t.planes)
    with pytest.raises(ValueError, match="device operations"):
        tr.reduce(t)


def test_unknown_device_kind_is_an_error():
    assert cost.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        cost.peaks("cpu")


def test_work_counts():
    flops, nbytes = cost.aggregation_work(n=10, nnz=30, f=4)
    assert flops == 2 * 30 * 4
    assert nbytes == 30 * 8 + 11 * 4 + 2 * 10 * 4 * 4
    peak = cost.peaks("TPU v5 lite")
    t, bound = cost.least_time(flops, nbytes, peak)
    assert bound == "bytes" and t == pytest.approx(nbytes / 819e9)
    t, bound = cost.least_time(1e15, 1.0, peak)
    assert bound == "flops" and t == pytest.approx(1e15 / 197e12)
    model = {"kind": "gcn", "d_in": 128, "d_hidden": 128, "n_classes": 40, "n_layers": 2}
    # the arxiv-shaped request: ~7.8 GFLOP of which the aggregations ~0.45
    total = cost.model_flops(model, 169_343, 1_335_586)
    assert total == pytest.approx(2 * 169_343 * (128 * 128 + 128 * 40) + 2 * 1_335_586 * 168)


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(REPO / "chipbench" / "run.py"), "--workload", "gcn-paper.arxiv",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0
    assert "refusing" in p.stderr
    assert not any(line.lstrip().startswith("{") for line in p.stdout.splitlines())


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_file_finds_every_piece_by_name():
    """Every name in BENCHMARK.json has its file: configuration, its
    model's kind (with the functions the harness calls), traffic mix and
    metric reader; every cell reports set-up, another end-to-end metric
    and a per-layer metric."""
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    assert bench["paths"] == ["chipbench"]
    for c in bench["configs"]:
        assert NAME.match(c["name"]) and (REPO / c["file"]).exists()
        kind = harness.kind_module(harness.read_json(REPO / c["file"])["model"])
        for fn in ("weights", "program_config", "host_weights", "model_flops", "aggregations"):
            assert callable(getattr(kind, fn)), (c["name"], fn)
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        harness.cell_spec(bench, w["name"])
        e2e = {m["name"] for m in harness.metrics_for(bench, w["name"], trace=False)}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert harness.metrics_for(bench, w["name"], trace=True)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"])
        assert callable(harness.metric_reader(m["name"]).read)
    for m in bench["per_layer"]:
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}


def _table_i_cells() -> list:
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    return [w["name"] for w in bench["workloads"]
            if harness.cell_spec(bench, w["name"])[2]["source"]["kind"] == "table_i"]


@pytest.mark.parametrize("workload", _table_i_cells())
def test_full_graph_cells_run_at_their_graphs_widths(workload):
    """A Table I cell's configuration reads as many input features as its
    traffic's graph has (Table I, ``simul.datasets.TABLE_I``), and the
    traffic draws the graph at its published nodes and edges."""
    from repro.simul.datasets import TABLE_I

    _, config, traffic = harness.cell_spec(harness.benchmark(), workload)
    src = traffic["source"]
    spec = TABLE_I[src["name"]]
    assert config["model"]["d_in"] == spec.feature_size
    assert (src["nodes"], src["edges"]) == (spec.nodes, spec.edges)
