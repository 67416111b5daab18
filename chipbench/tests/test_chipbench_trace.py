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
    the benchmark's host spans and the window in it."""
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
                with harness.span("chipbench.submit"):
                    f(x).block_until_ready()
    t = tr.load(str(tmp_path))
    names = {n for n, _, _ in t.host_spans}
    assert {"chipbench.window", "chipbench.submit"} <= names
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
    model = {"d_in": 128, "d_hidden": 128, "n_classes": 40, "n_layers": 2}
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
    """Every name in BENCHMARK.json has its file: configuration, traffic
    mix and metric reader; every cell reports set-up, another end-to-end
    metric and a per-layer metric."""
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    assert bench["paths"] == ["chipbench"]
    for c in bench["configs"]:
        assert NAME.match(c["name"]) and (REPO / c["file"]).exists()
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
