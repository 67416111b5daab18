"""Tests for tools/scvlint (scvcheck leg 3).

Each rule on synthetic snippets (fire + non-fire), pragma suppression,
the baseline engine, and the gate itself: the repo must lint clean
against the checked-in baseline (the same invocation scripts/lint.sh
makes).
"""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)  # tools/ is importable from the repo root

from tools.scvlint import (  # noqa: E402
    RULES,
    Violation,
    check_paths,
    check_source,
    load_baseline,
    main,
)


def _rules(src, rel="src/repro/fake.py"):
    return [(v.rule, v.line) for v in check_source(src, rel)]


# ---------------------------------------------------------------------------
# SCV001 — np.* in traced bodies
# ---------------------------------------------------------------------------
def test_scv001_jit_decorator():
    src = (
        "import numpy as np, jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return np.sum(x)\n"
    )
    assert _rules(src) == [("SCV001", 4)]


def test_scv001_defvjp_and_module_level_jit():
    src = (
        "import numpy as np, jax\n"
        "def fwd(x):\n"
        "    return np.asarray(x), None\n"
        "def other(x):\n"
        "    return np.asarray(x)\n"
        "f = jax.custom_vjp(lambda x: x)\n"
        "f.defvjp(fwd, fwd)\n"
        "g = jax.jit(other)\n"
    )
    rules = _rules(src)
    assert ("SCV001", 3) in rules  # fwd registered via defvjp
    assert ("SCV001", 5) in rules  # other wrapped by module-level jit


def test_scv001_kernel_prefix_scoped_to_kernels_tree():
    src = (
        "import numpy as np\n"
        "def _kernel_body(ref):\n"
        "    return np.sum(ref)\n"
    )
    assert _rules(src, "src/repro/kernels/scv_spmm/k.py") == [("SCV001", 3)]
    assert _rules(src, "benchmarks/run.py") == []  # host-side driver idiom


def test_scv001_untraced_function_clean():
    src = (
        "import numpy as np\n"
        "def host(x):\n"
        "    return np.sum(x)\n"
    )
    assert _rules(src) == []


def test_scv001_calling_a_jitted_fn_does_not_taint_args():
    # `forward_jit(batch(x))` must not mark `batch` as traced
    src = (
        "import numpy as np, jax\n"
        "def batch(x):\n"
        "    return np.asarray(x)\n"
        "forward_jit = jax.jit(lambda x: x)\n"
        "def serve(x):\n"
        "    return forward_jit(batch(x))\n"
    )
    assert _rules(src) == []


# ---------------------------------------------------------------------------
# SCV002 — magic constants duplicating core/scv.py
# ---------------------------------------------------------------------------
def test_scv002_ratio_and_chunk():
    src = (
        "RATIO = 1 / 16\n"
        "r2 = 0.0625\n"
        "chunk_size = 128\n"
        "def f(x, feature_chunk=128):\n"
        "    return x\n"
    )
    rules = [r for r, _ in _rules(src)]
    assert rules.count("SCV002") == 4


def test_scv002_owner_file_and_unrelated_literals_exempt():
    src = "MXU_VPU_RATIO = 1 / 16\nDEFAULT_CHUNK = 128\n"
    assert _rules(src, "src/repro/core/scv.py") == []
    # 128 bound to a non-chunk name is fine; so is dividing by other values
    assert _rules("block = 128\nx = 1 / 8\n") == []


def test_scv002_tunable_constants_in_repro_scope():
    src = (
        "tile = 64\n"
        "cap: int = 32\n"
        "def build(tile=128):\n"
        "    return tile\n"
        "bucket_caps = (8, 32)\n"
        "serve_ladder = [16, 64]\n"
    )
    rules = [r for r, _ in _rules(src)]
    assert rules.count("SCV002") == 5


def test_scv002_tunable_constants_scoped_and_owned():
    src = "tile = 64\nbucket_caps = (8, 32)\n"
    # benchmarks/tests sweep candidate values by design — out of scope
    assert _rules(src, "benchmarks/figures.py") == []
    assert _rules(src, "tests/test_foo.py") == []
    # TunedConfig is the other sanctioned owner
    assert _rules(src, "src/repro/tune/config.py") == []
    # non-literal bindings thread constants legitimately
    clean = (
        "from repro.core.scv import DEFAULT_LADDER, DEFAULT_TILE\n"
        "tile = DEFAULT_TILE\n"
        "bucket_caps = DEFAULT_LADDER\n"
        "def f(tile=DEFAULT_TILE):\n"
        "    return tile\n"
    )
    assert _rules(clean) == []


# ---------------------------------------------------------------------------
# SCV003 — nondiff_argnums over plan leaves
# ---------------------------------------------------------------------------
def test_scv003_plan_leaf_positions():
    src = (
        "import jax, functools\n"
        "@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 2))\n"
        "def f(tile_row, x, vals):\n"
        "    return x\n"
    )
    vs = check_source(src, "src/repro/fake.py")
    assert [v.rule for v in vs] == ["SCV003"]
    assert "tile_row" in vs[0].message and "vals" in vs[0].message


def test_scv003_static_config_positions_clean():
    src = (
        "import jax, functools\n"
        "@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))\n"
        "def f(vals, z, tile, n_rows):\n"
        "    return z\n"
    )
    assert _rules(src) == []


# ---------------------------------------------------------------------------
# SCV004 — jax shim pin hygiene
# ---------------------------------------------------------------------------
SHIM = (
    "try:\n"
    "    from jax import shard_map\n"
    "except ImportError:\n"
    "    from jax.experimental.shard_map import shard_map\n"
)


def test_scv004_unpinned_shim_flagged():
    assert _rules(SHIM) == [("SCV004", 1)]


def test_scv004_pinned_shim_clean():
    pinned = "# jax >= 0.6 re-homes shard_map; drop the except branch then.\n" + SHIM
    assert _rules(pinned) == []


def test_scv004_non_jax_shims_exempt():
    src = (
        "try:\n"
        "    import tomllib\n"
        "except ImportError:\n"
        "    tomllib = None\n"
    )
    assert _rules(src) == []


# ---------------------------------------------------------------------------
# SCV005 — fori_loop(unroll=)
# ---------------------------------------------------------------------------
def test_scv005_unroll_flagged():
    src = (
        "import jax\n"
        "def body(n, x):\n"
        "    return jax.lax.fori_loop(0, n, lambda i, c: c, x, unroll=4)\n"
    )
    assert _rules(src) == [("SCV005", 3)]
    clean = src.replace(", unroll=4", "")
    assert _rules(clean) == []


# ---------------------------------------------------------------------------
# SCV006 — full rebuilds inside src/repro/stream/
# ---------------------------------------------------------------------------
def test_scv006_rebuild_in_stream_flagged():
    src = (
        "from repro.core import coo_to_scv_tiles\n"
        "def patch(coo, delta):\n"
        "    return coo_to_scv_tiles(coo, 64)\n"
    )
    assert _rules(src, "src/repro/stream/delta.py") == [("SCV006", 3)]
    # dotted form fires too
    dotted = (
        "from repro import core\n"
        "def patch(coo, delta):\n"
        "    return core.plan_from_tiles_bucketed(core.coo_to_scv_tiles(coo, 64))\n"
    )
    assert {r for r, _ in _rules(dotted, "src/repro/stream/delta.py")} == {"SCV006"}


def test_scv006_scoped_to_stream_package():
    src = (
        "from repro.core import coo_to_scv_tiles\n"
        "def build(coo):\n"
        "    return coo_to_scv_tiles(coo, 64)\n"
    )
    # rebuilds are the whole point everywhere else
    assert _rules(src, "src/repro/serve/graph_engine.py") == []
    assert _rules(src, "benchmarks/run.py") == []
    assert _rules(src, "tests/test_stream.py") == []


# ---------------------------------------------------------------------------
# SCV007 — self.queue ownership in serve/
# ---------------------------------------------------------------------------
def test_scv007_direct_queue_mutation_flagged():
    src = (
        "class Engine:\n"
        "    def __init__(self):\n"
        "        self.queue = []\n"
        "    def submit(self, req):\n"
        "        self.queue.append(req)\n"
        "    def take(self):\n"
        "        self.queue = self.queue[2:]\n"
        "    def restore(self, batch):\n"
        "        self.queue[:0] = batch\n"
        "    def drop(self):\n"
        "        del self.queue[0]\n"
    )
    assert sorted(_rules(src, "src/repro/serve/other_engine.py"),
                  key=lambda rl: rl[1]) == [
        ("SCV007", 3), ("SCV007", 5), ("SCV007", 7), ("SCV007", 9),
        ("SCV007", 11),
    ]


def test_scv007_scoped_to_serve_outside_scheduler():
    # the scheduler/intake module owns the queue — exempt by design
    src = "class S:\n    def __init__(self):\n        self.queue = []\n"
    assert _rules(src, "src/repro/serve/scheduler.py") == []
    # outside serve/ other queues are unrelated
    assert _rules(src, "src/repro/train/loop.py") == []
    assert _rules(src, "tests/test_serve_graph.py") == []
    # reads and non-mutating calls don't fire; neither does someone
    # else's queue attribute
    clean = (
        "class Engine:\n"
        "    def peek(self):\n"
        "        return self.queue[0]\n"
        "    def depth(self):\n"
        "        return len(self.queue)\n"
        "    def relay(self):\n"
        "        self.scheduler.queue.put(1)\n"
    )
    assert _rules(clean, "src/repro/serve/graph_engine.py") == []


# ---------------------------------------------------------------------------
# pragmas, baseline, CLI
# ---------------------------------------------------------------------------
def test_pragma_suppression():
    src = (
        "import numpy as np, jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return np.sum(x)  # scvlint: ignore[SCV001]\n"
    )
    assert _rules(src) == []
    # rule-specific pragma does not blanket other rules
    src2 = "chunk = 128  # scvlint: ignore[SCV001]\n"
    assert _rules(src2) == [("SCV002", 1)]
    src3 = "chunk = 128  # scvlint: ignore\n"
    assert _rules(src3) == []


def test_baseline_keys_survive_line_drift(tmp_path):
    v = Violation(path="a.py", line=10, col=1, rule="SCV002",
                  message="m", source_line="chunk = 128")
    moved = Violation(path="a.py", line=99, col=1, rule="SCV002",
                      message="m", source_line="chunk = 128")
    assert v.baseline_key == moved.baseline_key
    bl = tmp_path / "baseline.txt"
    bl.write_text(f"# header\n{v.baseline_key}\n")
    assert load_baseline(str(bl)) == {v.baseline_key}


def test_main_exit_codes(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("chunk = 128\n")
    empty_bl = tmp_path / "bl.txt"
    empty_bl.write_text("")
    assert main([str(bad), "--baseline", str(empty_bl)]) == 1
    # --write-baseline accepts it; second run is then clean
    assert main([str(bad), "--baseline", str(empty_bl), "--write-baseline"]) == 0
    assert main([str(bad), "--baseline", str(empty_bl)]) == 0
    # --no-baseline resurrects it
    assert main([str(bad), "--no-baseline", "--baseline", str(empty_bl)]) == 1


def test_rules_registry_complete():
    assert set(RULES) == {
        "SCV001", "SCV002", "SCV003", "SCV004", "SCV005", "SCV006",
        "SCV007",
    }


# ---------------------------------------------------------------------------
# the gate: the repo lints clean against the checked-in baseline
# ---------------------------------------------------------------------------
def test_repo_lints_clean():
    proc = subprocess.run(
        [sys.executable, "-m", "tools.scvlint", "src/", "tools/", "benchmarks/"],
        cwd=REPO, capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_known_exceptions_carry_pragmas():
    """The two deliberate host-side exceptions stay pragma'd, not silently
    baselined: float0 cotangents (ops.py) and the linter's own ratio
    literal."""
    ops = os.path.join(REPO, "src/repro/kernels/scv_spmm/ops.py")
    with open(ops) as f:
        assert "scvlint: ignore[SCV001]" in f.read()
    vs = check_paths([ops], repo_root=REPO)
    assert [v for v in vs if v.rule == "SCV001"] == []
