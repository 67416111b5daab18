"""Model kinds (``kinds/<kind>.py``): the GCN kind gives the weights, the
program configuration, the reference's weights and the work counts that
the harness made before it took kinds from files, and a kind that is not
GCN, added as files alone, runs through the harness."""
import pathlib
from types import SimpleNamespace

import numpy as np
import pytest

import tinycells as T
import cost
import harness
from repro.models import gnn

HERE = pathlib.Path(__file__).resolve().parent
GCN_CONFIGS = ["gcn-paper", "gcn-cobuy", "gcn-molhiv"]
#: (nodes, entries): arxiv's and CoBuy Computer's graphs, and a molecule
SHAPES = [(169_343, 1_335_586), (13_752, 505_474), (25, 80)]
KIND_FUNCTIONS = ("weights", "program_config", "host_weights", "model_flops", "aggregations")


def _config(name: str) -> dict:
    return harness.read_json(harness.ROOT / "configs" / f"{name}.json")


# the harness's GCN arithmetic before kinds, kept as it was
def _gcn_dims(model):
    dims = [model["d_in"]] + [model["d_hidden"]] * (model["n_layers"] - 1) + [model["n_classes"]]
    return list(zip(dims[:-1], dims[1:]))


def _gcn_weights(model, seed):
    import jax
    import jax.numpy as jnp

    dims = _gcn_dims(model)

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(dims))
        return {
            f"layer{i}": {
                "w": jax.random.normal(k, (di, do), jnp.float32) * np.sqrt(2.0 / (di + do))
            }
            for i, (k, (di, do)) in enumerate(zip(keys, dims))
        }

    return build(jax.random.PRNGKey(harness.derived_seed(seed, "weights")))


@pytest.mark.parametrize("seed", [11, 2**31 + 4093])
@pytest.mark.parametrize("name", GCN_CONFIGS)
def test_gcn_kind_gives_the_weights_config_and_host_weights_of_before(name, seed):
    config = _config(name)
    model = config["model"]
    got, want = harness.make_weights(model, seed), _gcn_weights(model, seed)
    assert sorted(got) == sorted(want) == [f"layer{i}" for i in range(model["n_layers"])]
    for layer in want:
        assert list(got[layer]) == ["w"]
        a, b = np.asarray(got[layer]["w"]), np.asarray(want[layer]["w"])
        assert a.dtype == np.float32 and a.shape == b.shape and a.tobytes() == b.tobytes()

    _, mcfg = harness.build_engine(config, got, None, "pallas")
    assert mcfg == gnn.GNNConfig(
        name=config["name"], kind=model["kind"], d_in=model["d_in"], d_hidden=model["d_hidden"],
        n_classes=model["n_classes"], n_layers=model["n_layers"], backend="pallas",
    )

    host = harness.Workload.weights_host(SimpleNamespace(config=config, params=got))
    before = [np.asarray(got[f"layer{i}"]["w"]) for i in range(model["n_layers"])]
    assert len(host) == len(before)
    assert all(a.tobytes() == b.tobytes() for a, b in zip(host, before))


@pytest.mark.parametrize("name", GCN_CONFIGS)
def test_gcn_kind_counts_the_work_as_before(name):
    model = _config(name)["model"]
    peak = cost.peaks("TPU v5 lite")
    dims = _gcn_dims(model)
    for n, nnz in SHAPES:
        assert cost.model_flops(model, n, nnz) == sum(2.0 * n * di * do + 2.0 * nnz * do for di, do in dims)
        total, bound = 0.0, "bytes"
        for _, do in dims:
            t, b = cost.least_time(*cost.aggregation_work(n, nnz, do), peak)
            total += t
            bound = b
        assert cost.aggregation_least_time(model, n, nnz, peak) == (total, bound)
        aggs = harness.kind_module(model).aggregations(model, n, nnz)
        assert [w for w, _, _ in aggs] == [do for _, do in dims]


def test_an_unknown_kind_fails_at_set_up_naming_the_kinds():
    cfg = T.config()
    cfg["model"]["kind"] = "nosuch"
    with pytest.raises(KeyError, match=r"no model kind 'nosuch'.*known: \[.*'gcn'"):
        T.execute(cfg, T.full_graph_traffic(), seconds=0.3)


# ---------------------------------------------------------------------------
# a kind that is not GCN: the tests' ``sage``, from files in tests/kinds/
# ---------------------------------------------------------------------------
@pytest.fixture
def sage_kinds(monkeypatch):
    """The harness finds kinds in ``tests/kinds/``; the program's forward
    traces anew before and after the test."""
    monkeypatch.setattr(harness, "KINDS", HERE / "kinds")
    gnn.gnn_forward_jit.clear_cache()
    yield
    gnn.gnn_forward_jit.clear_cache()


def _sage(molecules: bool = False) -> dict:
    cfg = T.config(molecules=molecules)
    cfg["model"]["kind"] = "sage"
    cfg["reference"] = "tests/kinds/reference_sage"
    return cfg


def _sage_cell(traffic: str) -> tuple[dict, dict]:
    if traffic == "full_graph":
        return _sage(), T.full_graph_traffic()
    return _sage(molecules=True), T.stream_traffic()


def test_the_sage_kind_has_the_functions_and_its_own_counts(sage_kinds):
    model = _sage()["model"]
    kind = harness.kind_module(model)
    assert all(callable(getattr(kind, f)) for f in KIND_FUNCTIONS)
    dims = [(8, 16), (16, 4)]
    assert cost.model_flops(model, 10, 30) == sum(4.0 * 10 * di * do + 2.0 * 30 * do for di, do in dims)
    params = harness.make_weights(model, 3)
    assert [sorted(params[f"layer{i}"]) for i in range(2)] == [["w_neigh", "w_self"]] * 2


@pytest.mark.parametrize("traffic", ["full_graph", "stream"])
def test_a_sage_kind_added_as_files_runs_correct(sage_kinds, traffic):
    cfg, tr = _sage_cell(traffic)
    line = T.execute(cfg, tr, seconds=0.5)
    assert line["correct"], line["checks"]
    assert line["attempted"] >= 2 and line["failed"] == 0
    assert line["checks"]["out_gap"]["value"] < cfg["limits"]["out_gap"] / 10


@pytest.mark.parametrize("traffic", ["full_graph", "stream"])
def test_sage_with_the_neighbour_aggregation_left_out_is_not_correct(sage_kinds, monkeypatch, traffic):
    init, _ = gnn._LAYERS["sage"]

    def self_only(p, g, h, backend="jnp"):
        return h @ p["w_self"].astype(h.dtype)

    monkeypatch.setitem(gnn._LAYERS, "sage", (init, self_only))
    cfg, tr = _sage_cell(traffic)
    line = T.execute(cfg, tr, seconds=0.3)
    assert line["correct"] is False
    assert line["checks"]["out_gap"]["value"] > line["checks"]["out_gap"]["limit"]
