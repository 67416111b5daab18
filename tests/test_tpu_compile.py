"""The chip path compiles for a described TPU v5e (no chip attached).

The TPU compiler ships with jax and compiles for a chip that is described
rather than present.  These compiles catch what interpret mode cannot: a
block below the (8, 128) tiling, a dynamic lane slice Mosaic cannot
prove aligned, prefetched scalars that overflow SMEM.  Nothing runs, so
they say nothing about results or times.

The topology is described only inside the module fixture below: the
TPU library admits one process at a time, and under several test
workers only the worker given this file may load it.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.scv import DEFAULT_LADDER, SCVBucketedPlan, SCVPlan
from repro.kernels.scv_spmm.ops import MAX_LAUNCH_TILES
from repro.kernels.scv_spmm.scv_spmm import scv_spmm_pallas
from repro.tune.autotuner import CHUNK_CANDIDATES

T, FB = 64, 128
#: Rows of the arxiv-shaped graph (169,343 nodes), tile-aligned.
N_ROWS = 169_344


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs in /tmp
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache, so keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("chunk", CHUNK_CANDIDATES)
@pytest.mark.parametrize("accumulate", [False, True], ids=["first", "acc"])
@pytest.mark.parametrize("cap", DEFAULT_LADDER)
def test_kernel_compiles_for_v5e(one_chip, no_persistent_cache, cap, accumulate, chunk):
    """One launch at the most tiles a launch takes, at every ladder cap
    and tuner chunk, as the chain's first and accumulate-mode link."""
    nt = MAX_LAUNCH_TILES
    args = [_spec(one_chip, (nt,), jnp.int32)] * 3
    args += [_spec(one_chip, (nt, cap), jnp.int32)] * 2
    args += [_spec(one_chip, (nt, cap), jnp.float32)]
    args += [_spec(one_chip, (N_ROWS, FB), jnp.float32)]
    if accumulate:
        args += [_spec(one_chip, (N_ROWS, FB), jnp.float32)]

    def launch(*a):
        return scv_spmm_pallas(
            *a, tile=T, n_rows=N_ROWS, feature_block=FB, chunk=chunk
        )

    compiled = jax.jit(launch).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_gcn_paper_forward_compiles_for_v5e(one_chip, no_persistent_cache):
    """The gcn-paper forward over an arxiv-sized composite plan: the
    8-cap segment is 2^20 tiles, so it also compiles the span chain."""
    from repro.configs.gcn_paper import spec
    from repro.models.gnn import Graph, gnn_forward_jit, init_gnn
    from repro.serve.graph_engine import plan_launches

    cfg = spec.config
    assert cfg.backend == "pallas"
    params = jax.eval_shape(lambda: init_gnn(jax.random.PRNGKey(0), cfg)[0])
    params = jax.tree.map(lambda s: _spec(one_chip, s.shape, s.dtype), params)

    def segment(nt, cap):
        i32 = lambda *shape: _spec(one_chip, shape, jnp.int32)
        return SCVPlan(
            tile_row=i32(nt), tile_col=i32(nt), rows=i32(nt, cap),
            cols=i32(nt, cap), vals=_spec(one_chip, (nt, cap), jnp.float32),
            nnz_in_tile=i32(nt), perm=None, tile=T, cap=cap,
            shape=(N_ROWS, N_ROWS), order="zmorton",
        )

    plan = SCVBucketedPlan(tuple(
        segment(nt, cap) for nt, cap in zip((1 << 20, 8192, 4096), DEFAULT_LADDER)
    ))
    graph = Graph(n_nodes=N_ROWS, plan=plan)
    x = _spec(one_chip, (N_ROWS, cfg.d_in), jnp.float32)
    compiled = gnn_forward_jit.lower(params, cfg, graph, x).compile()
    launches = compiled.as_text().count("tpu_custom_call")
    assert launches == cfg.n_layers * plan_launches(plan) > 2 * cfg.n_layers
