"""GNN model zoo (the paper's own family): GCN, GraphSAGE, GIN, GAT.

All models express aggregation through ``repro.core.aggregate`` so any
sparse backend (CSR / CSC / SCV / SCV-Z / Pallas kernel) is a drop-in —
this is the paper's technique as a first-class framework feature, and it
is *trainable*: edge weights flow through the kernel's custom VJP (the
paper's future-work item (i)).

``Graph`` and ``BatchedGraph`` are registered jax pytrees wrapping a plan
(single-cap ``SCVPlan``, nnz-bucketed ``SCVBucketedPlan``, or a
mesh-placed ``core.exec.ShardedPlan``): device arrays are leaves,
counts/offsets are static aux data.  ``gnn_forward`` and
``gnn_forward_batched`` therefore run under a single outer ``jax.jit``
(``gnn_forward_jit`` is the prebuilt wrapper) — every layer's combination
*and* aggregation compiles into one XLA program, with retraces bounded by
the padding buckets because jit keys only on leaf shapes + static aux.
Per-edge attention (GAT) re-weights the plan's tile values through its
``perm`` leaf.

Device placement is the plan's business, not the model's:
``core.exec.PlanExecutor.prepare_graph`` swaps the plan for a
``ShardedPlan`` (mesh + sharding decision in its static aux), and the
same ``gnn_forward`` then compiles to a multi-device program — the
``shard_map`` aggregation launches (one boundary ``psum`` over the
``"tiles"`` axis, feature slabs collective-free) sit inside the one XLA
program like any other op.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.aggregate import aggregate_scv_plan
from repro.core.formats import COOMatrix, block_diag_coo
from repro.core.scv import (
    DEFAULT_TILE,
    SCVBucketedPlan,
    SCVPlan,
    coo_to_scv_tiles,
    host_plan_from_tiles,
    host_plan_from_tiles_bucketed,
    to_device,
)
from repro.models.layers import make_param, split_tree


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Graph:
    """Device-ready graph plan, registered as a jax pytree.

    Leaves: the ``SCVPlan`` (itself a pytree) and the optional COO edge
    arrays (``rows`` / ``cols`` / ``vals`` — only GAT's attention reads
    them; batched composites may omit them, see
    ``serve.graph_engine.assemble_batched_graph``).  Static aux:
    ``n_nodes``.  ``build_host_graph`` returns one with numpy leaves,
    which ``core.scv.to_device`` puts.
    """

    n_nodes: int
    plan: "SCVPlan | SCVBucketedPlan | ShardedPlan"
    rows: Optional[jnp.ndarray] = None  # i32[E] (normalized adjacency entries)
    cols: Optional[jnp.ndarray] = None
    vals: Optional[jnp.ndarray] = None  # f32[E] normalized weights (GCN) or 1s

    def tree_flatten(self):
        return (self.plan, self.rows, self.cols, self.vals), (self.n_nodes,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(aux[0], *children)


def build_host_graph(
    adj: COOMatrix,
    tile: int = DEFAULT_TILE,
    backend_cap: Optional[int] = None,
    with_edges: bool = True,
    bucket_caps=None,
    config=None,
) -> Graph:
    """COO adjacency -> :class:`Graph` with numpy leaves: the host stage
    of :func:`build_graph`.  The serving engine keeps its member plans
    here, since composite assembly reads them only on the host.

    ``bucket_caps`` selects the nnz-bucketed plan layout: ``"auto"``
    derives the capacity ladder from the tile nnz histogram
    (``core.scv.bucket_caps_for``); an explicit ascending tuple pins it
    (serving uses a fixed ladder so every member plan shares segment aux).
    ``None`` keeps the single-cap :class:`SCVPlan`.  When a ladder is
    active it supersedes ``backend_cap`` entirely (heavy tiles chain-split
    at ``caps[-1]``, the per-segment caps come from the ladder).

    ``config`` — a ``repro.tune.TunedConfig`` (mutually exclusive with
    the explicit layout arguments): its tile and ladder (or single cap
    when the ladder is empty) define the whole layout, so an autotuned
    regime threads through as one object.
    """
    if config is not None:
        if bucket_caps is not None or backend_cap is not None or tile != DEFAULT_TILE:
            raise ValueError(
                "config carries tile/cap/ladder; don't also pass them explicitly"
            )
        tile = config.tile
        if config.bucket_caps:
            bucket_caps = tuple(config.bucket_caps)
        else:
            backend_cap = config.cap
    if bucket_caps is not None and backend_cap is not None:
        raise ValueError(
            "backend_cap and bucket_caps are mutually exclusive: the "
            "bucket ladder defines every capacity (chain-split at caps[-1])"
        )
    if bucket_caps is not None:
        if bucket_caps == "auto":
            from repro.core.scv import bucket_caps_for, tile_nnz_histogram

            caps = bucket_caps_for(tile_nnz_histogram(adj, tile), tile)
        else:
            caps = tuple(int(c) for c in bucket_caps)
            if list(caps) != sorted(set(caps)) or caps[0] <= 0:
                raise ValueError(
                    f"bucket_caps must be ascending distinct positives, got {caps}"
                )
        # chain-split heavy tiles at the ladder's largest cap so every
        # chain fits some bucket
        tiles = coo_to_scv_tiles(adj, tile, cap=caps[-1])
        plan = host_plan_from_tiles_bucketed(tiles, caps=caps)
    else:
        tiles = coo_to_scv_tiles(adj, tile, cap=backend_cap)
        plan = host_plan_from_tiles(tiles)  # coverage dummies + perm padding, one path
    rows = cols = vals = None
    if with_edges:
        # copies: the plan must not alias the caller's adjacency
        rows, cols, vals = (
            np.array(a, jax.dtypes.canonicalize_dtype(a.dtype))
            for a in (adj.rows, adj.cols, adj.vals)
        )
    return Graph(n_nodes=adj.shape[0], plan=plan, rows=rows, cols=cols, vals=vals)


def build_graph(
    adj: COOMatrix,
    tile: int = DEFAULT_TILE,
    backend_cap: Optional[int] = None,
    with_edges: bool = True,
    bucket_caps=None,
    config=None,
) -> Graph:
    """COO adjacency -> device-ready :class:`Graph`: the host stage
    (:func:`build_host_graph`, which documents the arguments) put to the
    device."""
    return to_device(
        build_host_graph(adj, tile, backend_cap, with_edges, bucket_caps, config)
    )


def _agg(g: Graph, z, edge_vals=None, backend="jnp"):
    """Aggregate with optional per-edge re-weighting (GAT).

    ``aggregate_scv_plan`` dispatches on the plan kind — a mesh-placed
    ``ShardedPlan`` runs the executor's shard_map launch; the layers never
    know where the plan lives."""
    plan = g.plan
    if edge_vals is not None:
        # perm == -1 (padding slot) gathers an appended zero; bucketed
        # and sharded plans re-gather per capacity segment
        plan = plan.reweighted(edge_vals)
    return aggregate_scv_plan(plan, z, backend=backend)[: g.n_nodes]


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------


def init_gcn_layer(key, d_in, d_out):
    return {"w": make_param(key, (d_in, d_out), ("gnn_in", "gnn_out"))}


def gcn_layer(p, g: Graph, h, backend="jnp"):
    z = h @ p["w"].astype(h.dtype)  # combination, Eq. (2)
    return _agg(g, z, backend=backend)  # aggregation, Eq. (3)


def init_sage_layer(key, d_in, d_out):
    k1, k2 = jax.random.split(key)
    return {
        "w_self": make_param(k1, (d_in, d_out), ("gnn_in", "gnn_out")),
        "w_neigh": make_param(k2, (d_in, d_out), ("gnn_in", "gnn_out")),
    }


def sage_layer(p, g: Graph, h, backend="jnp"):
    neigh = _agg(g, h @ p["w_neigh"].astype(h.dtype), backend=backend)
    return h @ p["w_self"].astype(h.dtype) + neigh


def init_gin_layer(key, d_in, d_out):
    k1, k2 = jax.random.split(key)
    return {
        "w1": make_param(k1, (d_in, d_out), ("gnn_in", "gnn_out")),
        "w2": make_param(k2, (d_out, d_out), ("gnn_in", "gnn_out")),
        "eps": (jnp.zeros((), jnp.float32), ()),
    }


def gin_layer(p, g: Graph, h, backend="jnp"):
    agg = _agg(g, h, backend=backend)  # sum aggregation over raw features
    z = (1.0 + p["eps"]) * h + agg
    z = jax.nn.relu(z @ p["w1"].astype(h.dtype))
    return z @ p["w2"].astype(h.dtype)


def init_gat_layer(key, d_in, d_out):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w": make_param(k1, (d_in, d_out), ("gnn_in", "gnn_out")),
        "a_src": make_param(k2, (d_out,), ("gnn_out",)),
        "a_dst": make_param(k3, (d_out,), ("gnn_out",)),
    }


def gat_layer(p, g: Graph, h, backend="jnp"):
    """Single-head GAT: per-edge attention -> SCV aggregation with
    re-weighted values (weighted aggregation, §IV-D)."""
    if g.rows is None:
        raise ValueError(
            "GAT needs the graph's COO edge arrays; build the plan with "
            "with_edges=True (serving: assemble_batched_graph(with_edges=True))"
        )
    z = h @ p["w"].astype(h.dtype)
    e_src = z @ p["a_src"].astype(h.dtype)  # [N]
    e_dst = z @ p["a_dst"].astype(h.dtype)
    logits = jax.nn.leaky_relu(e_src[g.rows] + e_dst[g.cols], 0.2)
    # edge softmax per destination row (stable)
    rmax = jnp.full((g.n_nodes,), -1e30, logits.dtype).at[g.rows].max(logits)
    ex = jnp.exp(logits - rmax[g.rows])
    denom = jnp.zeros((g.n_nodes,), ex.dtype).at[g.rows].add(ex)
    alpha = ex / jnp.maximum(denom[g.rows], 1e-9)
    return _agg(g, z, edge_vals=alpha, backend=backend)


# ---------------------------------------------------------------------------
# models
# ---------------------------------------------------------------------------

_LAYERS = {
    "gcn": (init_gcn_layer, gcn_layer),
    "sage": (init_sage_layer, sage_layer),
    "gin": (init_gin_layer, gin_layer),
    "gat": (init_gat_layer, gat_layer),
}


@dataclasses.dataclass(frozen=True)
class GNNConfig:
    name: str
    kind: str  # gcn | sage | gin | gat
    d_in: int
    d_hidden: int
    n_classes: int
    n_layers: int = 2
    backend: str = "jnp"  # aggregation backend (pallas on TPU)


def init_gnn(key, cfg: GNNConfig):
    init_fn, _ = _LAYERS[cfg.kind]
    dims = [cfg.d_in] + [cfg.d_hidden] * (cfg.n_layers - 1) + [cfg.n_classes]
    tree = {}
    for i, k in enumerate(jax.random.split(key, cfg.n_layers)):
        tree[f"layer{i}"] = init_fn(k, dims[i], dims[i + 1])
    return split_tree(tree)


def gnn_forward(params, cfg: GNNConfig, g: Graph, x):
    """Full multi-layer forward.  Pure function of pytree arguments —
    ``g`` is a registered pytree and ``cfg`` is hashable — so the whole
    thing jits: see ``gnn_forward_jit``."""
    _, layer_fn = _LAYERS[cfg.kind]
    h = x
    for i in range(cfg.n_layers):
        h = layer_fn(params[f"layer{i}"], g, h, backend=cfg.backend)
        if i + 1 < cfg.n_layers:
            h = jax.nn.relu(h)
    return h


#: End-to-end jitted forward: one XLA program per (cfg, graph aux + leaf
#: shapes, x shape) — i.e. at most one trace per serving padding bucket.
gnn_forward_jit = jax.jit(gnn_forward, static_argnames=("cfg",))


# ---------------------------------------------------------------------------
# batched multi-graph forward (serving path)
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class BatchedGraph:
    """Many small graphs composed into one block-diagonal ``Graph``.

    Because the composite adjacency is block-diagonal, one aggregation
    launch over it equals the per-graph aggregations stacked.  Request i
    owns node rows ``node_offsets[i] : node_offsets[i] + node_counts[i]``;
    every other composite row is structural padding (members may sit at
    tile-aligned offsets, and the composite is grown to a padding bucket so
    jit sees few distinct shapes).  ``n_real_nodes`` is the total real node
    count across members — NOT a row boundary; always use the offset/count
    arrays to locate real rows.

    Pytree: the composite ``graph`` is the only leaf subtree; the offset /
    count arrays are static aux data (as int tuples), so the per-member
    scatter/split slices stay Python ints under jit.  Note this makes the
    member layout part of a jit trace signature — the serving engine
    therefore jits the composite ``gnn_forward`` (whose signature depends
    only on the padding bucket) and keeps the member bookkeeping eager.
    """

    graph: Graph
    node_offsets: np.ndarray  # int64[k+1] — request i starts at composite row off[i]
    node_counts: np.ndarray  # int64[k] — request i owns off[i] : off[i]+counts[i]
    n_real_nodes: int

    def tree_flatten(self):
        return (self.graph,), (
            tuple(int(o) for o in self.node_offsets),
            tuple(int(c) for c in self.node_counts),
            self.n_real_nodes,
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        off, cnt, n_real = aux
        return cls(
            graph=children[0],
            node_offsets=np.asarray(off, np.int64),
            node_counts=np.asarray(cnt, np.int64),
            n_real_nodes=n_real,
        )

    @property
    def n_graphs(self) -> int:
        return len(self.node_counts)


def build_batched_graph(
    adjs: list[COOMatrix],
    tile: int = DEFAULT_TILE,
    backend_cap: Optional[int] = None,
    pad_nodes: Optional[int] = None,
) -> BatchedGraph:
    """Compose per-request adjacencies into one device-ready Graph."""
    for a in adjs:
        if a.shape[0] != a.shape[1]:
            raise ValueError(f"adjacency must be square, got {a.shape}")
    n_real = int(sum(a.shape[0] for a in adjs))
    pad_shape = None
    if pad_nodes is not None:
        if pad_nodes < n_real:
            raise ValueError(f"pad_nodes={pad_nodes} < total nodes {n_real}")
        pad_shape = (pad_nodes, pad_nodes)
    comp, row_off, _ = block_diag_coo(adjs, pad_shape=pad_shape)
    g = build_graph(comp, tile=tile, backend_cap=backend_cap)
    return BatchedGraph(
        graph=g,
        node_offsets=row_off,
        node_counts=np.diff(row_off),
        n_real_nodes=n_real,
    )


def batch_features(bg: BatchedGraph, xs) -> jnp.ndarray:
    """Stack per-request feature matrices into the composite node space
    (zeros in padding rows).

    Works both eagerly (numpy fill, one host->device transfer) and under a
    jit trace (static-slice ``.at[].set`` updates — the offsets are static
    aux of ``bg``), so ``gnn_forward_batched`` is jit-able end to end.
    """
    if len(xs) != bg.n_graphs:
        raise ValueError(f"{len(xs)} feature blocks for {bg.n_graphs} graphs")
    if any(isinstance(xi, jax.core.Tracer) for xi in xs):
        d = int(xs[0].shape[1]) if xs else 0
        x = jnp.zeros((bg.graph.n_nodes, d), jnp.float32)
        for i, xi in enumerate(xs):
            s, c = int(bg.node_offsets[i]), int(bg.node_counts[i])
            x = x.at[s : s + c].set(xi.astype(jnp.float32))
        return x
    d = int(np.asarray(xs[0]).shape[1]) if xs else 0
    x = np.zeros((bg.graph.n_nodes, d), np.float32)
    for i, xi in enumerate(xs):
        s = int(bg.node_offsets[i])
        x[s : s + int(bg.node_counts[i])] = np.asarray(xi, np.float32)
    return jnp.asarray(x)


def split_outputs(bg: BatchedGraph, out) -> list:
    """Scatter the composite output back into per-request blocks.

    Eagerly, blocks are numpy copies, not views: a view would pin the whole
    bucket-sized composite alive for as long as any request retains its
    (much smaller) output.  Under a jit trace, blocks are static slices of
    the traced composite (XLA owns the buffers there).
    """
    spans = [
        (int(bg.node_offsets[i]), int(bg.node_counts[i]))
        for i in range(bg.n_graphs)
    ]
    if isinstance(out, jax.core.Tracer):
        return [out[s : s + c] for s, c in spans]
    host = np.asarray(out)
    return [host[s : s + c].copy() for s, c in spans]


def gnn_forward_batched(params, cfg: GNNConfig, bg: BatchedGraph, xs):
    """One forward over the block-diagonal composite; returns the
    per-request outputs (exactly ``gnn_forward`` on each graph, up to
    float-add reassociation across tile boundaries).

    The composite forward runs through ``gnn_forward_jit`` (nested jit is
    inlined when this function is itself traced), so the per-layer hot path
    never round-trips through Python dispatch; only the per-member
    scatter/split bookkeeping stays host-side when called eagerly.  The
    function is also directly wrappable in ``jax.jit`` (``bg`` is a pytree
    whose member layout is static aux).
    """
    out = gnn_forward_jit(params, cfg, bg.graph, batch_features(bg, xs))
    return split_outputs(bg, out)


def gnn_loss(params, cfg: GNNConfig, g: Graph, x, labels, mask):
    logits = gnn_forward(params, cfg, g, x)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
