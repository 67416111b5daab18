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

from repro.core.scv import DEFAULT_LADDER, SCVBucketedPlan, SCVPlan, uses_row_gather
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


#: (cap, accumulate, chunk, body, Z width): the vector body at every ladder
#: cap and tuner chunk, and the row-gather body at the caps it serves, at
#: gcn-paper's two aggregation widths (40 pads to one lane tile).
LAUNCHES = [
    pytest.param(cap, acc, chunk, "vector", FB,
                 id=f"{cap}-{'acc' if acc else 'first'}-{chunk}")
    for cap in DEFAULT_LADDER for acc in (False, True) for chunk in CHUNK_CANDIDATES
] + [
    pytest.param(cap, acc, 128, "rows", width,
                 id=f"rows-{cap}-{'acc' if acc else 'first'}-F{width}")
    for cap in DEFAULT_LADDER if uses_row_gather(cap, T, MAX_LAUNCH_TILES)
    for acc in (False, True) for width in (128, 40)
]


@pytest.mark.parametrize("cap,accumulate,chunk,body,width", LAUNCHES)
def test_kernel_compiles_for_v5e(
    one_chip, no_persistent_cache, cap, accumulate, chunk, body, width
):
    """One launch at the most tiles a launch takes, as the chain's first
    and accumulate-mode link."""
    nt = MAX_LAUNCH_TILES
    args = [_spec(one_chip, (nt,), jnp.int32)] * 3
    args += [_spec(one_chip, (nt, cap), jnp.int32)] * 2
    args += [_spec(one_chip, (nt, cap), jnp.float32)]
    args += [_spec(one_chip, (N_ROWS, width), jnp.float32)]
    if accumulate:
        args += [_spec(one_chip, (N_ROWS, FB), jnp.float32)]

    def launch(*a):
        a = list(a)
        a[6] = jnp.pad(a[6], ((0, 0), (0, FB - width)))  # as ops._pad_z does
        return scv_spmm_pallas(
            *a, tile=T, n_rows=N_ROWS, feature_block=FB, chunk=chunk, body=body
        )

    compiled = jax.jit(launch).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _row_gather_launches(hlo: str, plan) -> int:
    """Kernel launches in ``hlo`` that take a row-gather segment's entry
    values as they are, ``f32[nt, cap]``: the vector body takes them
    chunked, ``f32[nt, cap // C, C]``."""
    shapes = {
        f"f32[{min(s.n_tiles, MAX_LAUNCH_TILES)},{s.cap}]{{1,0}}"
        for s in plan.segments if uses_row_gather(s.cap, s.tile, s.n_tiles)
    }
    return sum(
        any(sh in line for sh in shapes)
        for line in hlo.splitlines() if "tpu_custom_call" in line
    )


def test_gcn_paper_forward_compiles_for_v5e(one_chip, no_persistent_cache):
    """The gcn-paper forward over an arxiv-sized composite plan: the
    8-cap segment is 2^20 tiles, so it also compiles the span chain, and
    it and the 32-cap segment run the row-gather body."""
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
    # the cap-8 and cap-32 segments lower as row-gather launches
    row_gather = cfg.n_layers * plan_launches(plan, row_gather=True)
    assert _row_gather_launches(compiled.as_text(), plan) == row_gather > 0
