"""Batched multi-graph SCV inference engine.

The GNN analogue of ``serve/engine.py``'s LM loop: requests carry a whole
graph (adjacency + node features + model kind) instead of a prompt, and a
"batch" is many small graphs fused into one block-diagonal adjacency so the
entire wave runs as **one** SCV aggregation launch per layer.

Three mechanisms make this a serving system rather than a loop:

1. **Plan cache** (``plan_cache.py``) — the §III-C host-side SCV build is
   content-addressed and LRU-cached (with an optional TTL) at two levels:
   per-graph ``Graph`` bundles (hot graphs skip preprocessing) and
   assembled composite batches (hot *batches* skip even the
   concatenation).

2. **Composite assembly from cached plans** — because every member plan is
   padded to the tile grid, a batch plan is pure index arithmetic over the
   members' ``SCVPlan`` pytrees: member tile coordinates are shifted by
   the member's block offset and the plan leaves concatenated (vectorized
   numpy — no Python loop over tiles).  No re-tiling, no re-sorting, no
   COO scan.  Member plans stay on the host (numpy leaves, as
   ``build_host_graph`` leaves them); only the composite goes to the
   device, in one put.  The block-diagonal structure guarantees the
   result equals per-graph aggregation stacked
   (``core.formats.block_diag_coo`` is the reference construction;
   ``tests/test_serve_graph.py`` checks both agree).  The composite COO edge arrays + perm are built only when the
   batch's model kind needs them (GAT) — which puts the model-kind
   component into the composite cache key (see ``_batch_plan``).

3. **Padding buckets** — composite node counts are rounded up to a fixed
   bucket ladder, so XLA sees a handful of distinct shapes instead of one
   per batch and jit recompilation is bounded.  A wave then runs through
   the end-to-end jitted ``gnn_forward`` over the composite plan pytree —
   a cache hit hands jit a ready device pytree and the whole multi-layer
   forward is one XLA program.

4. **Multi-device routing** — composites whose padded node count or total
   nnz exceed the ``GraphEngineConfig`` thresholds are placed by a
   ``core.exec.PlanExecutor`` (tile-span / feature-axis / 2-D sharding
   from workload numbers and the device pool) and execute through the
   same jitted forward — a ``ShardedPlan`` is just another plan kind.
   The sharding decision is part of the composite cache key, so hot
   oversized batches reuse their sharded layout.

The engine is single-host-process (like ``ServeEngine``); the launch/
layer owns process fan-out.  Intake is owned by ``serve/scheduler.py``:
the synchronous ``run()`` drains it in degenerate single-consumer waves,
while ``start()`` hands it to the continuous-batching scheduler loop
(mid-flight wave coalescing, deadline-aware admission, serialized
``update()`` control messages) — see the scheduler module docstring and
serve/README.md "Async serving".
"""
from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from typing import Optional

import jax
import numpy as np

from repro.core.exec import ShardedPlan
from repro.core.formats import COOMatrix
from repro.core.scv import (
    DEFAULT_CAP,
    DEFAULT_LADDER,
    DEFAULT_TILE,
    SCVBucketedPlan,
    SCVPlan,
    to_device,
    uses_row_gather,
)
from repro.core.validate import check_coo, validate_plan
from repro.kernels.scv_spmm.ops import n_launches
from repro.models.gnn import (
    BatchedGraph,
    GNNConfig,
    Graph,
    batch_features,
    build_host_graph,
    gnn_forward_jit,
    split_outputs,
)
from repro.serve.plan_cache import PlanCache, combine_keys, coo_content_key
from repro.serve.scheduler import (
    AdmissionRejected,
    EngineOverloaded,
    Scheduler,
    _Control,
)
from repro.spans import span
from repro.stream import DeltaBatch, apply_coo, apply_delta, check_delta
from repro.tune.config import TunedConfig

__all__ = [
    "AdmissionRejected",
    "EngineOverloaded",
    "GraphEngineConfig",
    "GraphRequest",
    "GraphServeEngine",
    "assemble_batched_graph",
    "plan_launches",
]


@dataclasses.dataclass
class GraphRequest:
    """One inference request: run ``model`` over (adj, x).

    ``adj`` may be omitted when ``graph_id`` names a graph the engine
    already tracks (registered by an earlier request that carried both) —
    the wave then serves the tracked graph's *current* adjacency, i.e.
    the state after every ``update()`` applied so far.
    """

    rid: int
    adj: Optional[COOMatrix] = None  # normalized adjacency (e.g. gcn_normalize)
    x: Optional[np.ndarray] = None  # f32[n_nodes, d_in]
    model: str = "default"
    # stable identity for delta-tracked graphs: requests carrying a
    # graph_id (re)register the adjacency under it, and later requests may
    # omit adj to serve the tracked (delta-updated) state
    graph_id: Optional[str] = None
    # latency budget in seconds, relative to submit time.  Admission
    # control rejects the request up front when the deadline is infeasible
    # at the current queue depth, and wave formation sheds it if the
    # estimate later degrades past the budget.  None = serve whenever.
    deadline_s: Optional[float] = None
    out: Optional[np.ndarray] = None  # f32[n_nodes, n_classes] when done
    done: bool = False
    error: Optional[str] = None  # set when ejected as failed or shed
    retries: int = 0  # failed waves this request has been part of
    isolate: bool = False  # re-serve alone (failure isolation)
    # the latest wave this request was formed into: the ``wave=`` id of
    # that wave's ``serve.*`` spans (see repro.spans)
    wave: Optional[int] = None
    t_submit: float = 0.0  # time.monotonic() at admission
    t_done: float = 0.0  # time.monotonic() at completion
    # set on every terminal transition (completed / failed / shed) —
    # async callers block on it via result()
    event: Optional[threading.Event] = None

    @property
    def latency_s(self) -> Optional[float]:
        return self.t_done - self.t_submit if self.done else None

    def result(self, timeout: Optional[float] = None) -> np.ndarray:
        """Block until this request reaches a terminal state; returns the
        output or raises ``RuntimeError`` with the failure/shed reason."""
        if self.event is not None and not self.event.wait(timeout):
            raise TimeoutError(f"request {self.rid} not done after {timeout}s")
        if self.error is not None:
            raise RuntimeError(f"request {self.rid}: {self.error}")
        if not self.done:
            raise RuntimeError(f"request {self.rid} is not done")
        return self.out


@dataclasses.dataclass
class GraphEngineConfig:
    max_batch_graphs: int = 16
    max_batch_nodes: int = 4096
    tile: int = DEFAULT_TILE
    cap: int = DEFAULT_CAP  # per-tile entry capacity when bucket_caps is off
    # nnz-bucketed plans: a fixed ascending capacity ladder shared by every
    # member plan (so composites fuse segment-by-segment and jit traces are
    # shared across batches).  ON by default — the serve_bench A/B
    # (BENCH_serve.json) gates bucketed >= single-cap throughput AND the
    # default ladder >= the measured ladder-depth winner.  With
    # accumulator-chained launches coverage dummies exist once per plan,
    # so ladder depth no longer pays a per-segment dummy set — the
    # remaining depth cost is one launch (one jnp pass on the serving
    # backend) per extra bucket; the 3-deep ladder won the interleaved
    # sweep on the sparse serving pool (ladder_ab in BENCH_serve.json;
    # 2/4-deep within ~5%).  Empty tuple selects the legacy single-cap plans
    # (``cap``); when the ladder is set it supersedes ``cap`` (heavy
    # tiles chain-split at ``bucket_caps[-1]``).
    bucket_caps: tuple[int, ...] = DEFAULT_LADDER
    # autotuned per-regime plan configuration (repro.tune): when on, each
    # distinct graph regime (quantized tile-nnz histogram x machine
    # fingerprint) resolves its own (tile, ladder) via the Autotuner
    # instead of the tile/cap/bucket_caps literals above, which then only
    # serve as the fallback for empty graphs.  Batches group by resolved
    # config (composite members must share tile and ladder), member and
    # composite cache keys carry the resolved layout, and ``metrics()``
    # reports every resolved config.  Resolution on a store hit costs one
    # O(nnz) histogram per request per wave; a miss runs the stage-1
    # simulator sweep (plus measured calibration when
    # ``autotune_calibrate`` is set — leave that to offline benches).
    autotune: bool = False
    autotune_store: Optional[str] = None  # TuneStore path (None = in-memory)
    autotune_calibrate: bool = False
    node_buckets: tuple[int, ...] = (256, 512, 1024, 2048, 4096)
    cache_entries: int = 256
    # bounds host-held member plans plus device-held composites together
    cache_bytes: int = 256 << 20
    plan_ttl_s: Optional[float] = None  # expire cached plans after this age
    completed_history: int = 1024  # recent requests kept for inspection
    max_retries: int = 1  # failed waves a request survives before ejection
    # multi-device routing (core.exec.PlanExecutor): a composite whose
    # padded node count exceeds shard_nodes_threshold OR whose total nnz
    # exceeds shard_nnz_threshold executes on the executor's sharded path.
    # None disables the corresponding trigger; both None = single-device
    # engine even when an executor is attached.
    shard_nodes_threshold: Optional[int] = None
    shard_nnz_threshold: Optional[int] = None
    # periodic re-anchoring of delta-tracked graphs: every N updates the
    # tracked entry is re-homed from its delta-chained lineage key to the
    # coo_content_key of the *current* adjacency (PlanCache.anchor), so an
    # untracked client submitting the same post-delta graph hits instead
    # of building a duplicate entry.  0 disables.
    anchor_every: int = 16
    # debug mode: run the full core.validate invariant chain on every
    # freshly *built* composite (cache hits were validated when built).
    # A malformed composite then fails loudly at the admission boundary
    # with a named invariant instead of producing wrong aggregations.
    # Costs a host-side pass over the plan leaves — leave off in
    # production, turn on when bisecting plan corruption.
    debug_validate: bool = False
    # --- async scheduler (serve/scheduler.py) ---------------------------
    # a forming wave absorbs compatible arrivals until it holds
    # target_wave_size graphs (None = max_batch_graphs) or this many
    # milliseconds have passed since its first member arrived; 0 disables
    # the absorb window (waves snapshot like the sync path)
    max_wave_delay_ms: float = 2.0
    target_wave_size: Optional[int] = None
    # bounded intake: submit() blocks (or raises EngineOverloaded with
    # block=False) when this many requests are queued — backpressure
    # instead of unbounded memory growth under overload
    intake_capacity: int = 4096
    # completed-request latencies retained for the metrics() percentiles
    latency_window: int = 4096
    # smoothing for the per-model wave service-time EMA that admission
    # control and deadline shedding estimate from
    service_ema_alpha: float = 0.2

    def __post_init__(self):
        for field in ("max_batch_graphs", "max_batch_nodes", "tile", "cap"):
            if getattr(self, field) <= 0:
                raise ValueError(f"{field} must be positive")
        if self.bucket_caps:
            caps = tuple(int(c) for c in self.bucket_caps)
            if list(caps) != sorted(set(caps)) or caps[0] <= 0:
                raise ValueError(
                    f"bucket_caps must be ascending distinct positives, got {caps}"
                )
        for field in ("shard_nodes_threshold", "shard_nnz_threshold"):
            v = getattr(self, field)
            if v is not None and v <= 0:
                raise ValueError(f"{field} must be positive (or None)")
        if self.anchor_every < 0:
            raise ValueError("anchor_every must be >= 0 (0 disables)")
        if self.completed_history < 0:
            raise ValueError("completed_history must be >= 0")
        if self.node_buckets and self.max_batch_nodes > max(self.node_buckets):
            # batches admitted past the ladder would each get a bespoke pad
            # size — unbounded jit recompiles, the thing buckets exist to stop
            raise ValueError(
                f"max_batch_nodes={self.max_batch_nodes} exceeds the largest "
                f"node bucket ({max(self.node_buckets)}); extend node_buckets "
                f"(or set node_buckets=() for power-of-two padding)"
            )


# ---------------------------------------------------------------------------
# composite assembly from per-graph plans
# ---------------------------------------------------------------------------
def _bucket_nodes(n: int, buckets: tuple[int, ...], tile: int) -> int:
    """Smallest bucket >= n; past the ladder (an oversized single request —
    wave formation always admits the head), round up to the next power of two
    so distinct jit shapes stay logarithmic in graph size rather than one
    per request."""
    for b in sorted(buckets):
        if b >= n:
            return -(-b // tile) * tile
    p = 1
    while p < n:
        p *= 2
    return -(-p // tile) * tile


def _cat(parts, pad_blocks, dtype):
    # convert per block BEFORE concatenating: mixing int32 members with
    # default-float64 pads would promote the whole composite to f64
    blocks = [np.asarray(p, dtype) for p in parts]
    blocks += [np.asarray(b, dtype) for b in pad_blocks]
    return np.concatenate(blocks) if blocks else np.zeros(0, dtype)


def _assemble_segment(
    segs: list[SCVPlan],
    blk_off: np.ndarray,
    n_aligned: int,
    pad_nodes: int,
    T: int,
    cap: int,
    order: str,
    entry_off: Optional[np.ndarray],
    first_segment: bool = True,
) -> SCVPlan:
    """Fuse one capacity segment across members into the composite segment
    (numpy leaves; ``assemble_batched_graph`` puts the whole composite).

    Member tile coordinates shift by the member's block offset; then two
    pad blocks follow: fresh zero-nnz coverage tiles for the bucket-padding
    block-rows at the tail — only in the *first* segment (its launch
    zero-defines the whole output; later launches chain through the
    aliased accumulator, so member plans and composites alike carry
    coverage once per plan) — then tile-count padding up to the next
    power of two so jit sees a bounded set of array shapes.  The
    tile-count padding repeats the *last* tile's coordinates: the kernel
    then revisits an already-initialized PS strip (no re-zeroing —
    appending a fresh block-row would wipe real output), and the jnp
    reference masks the zero-nnz slots via nnz_in_tile.

    ``entry_off`` (per-member edge-array offsets) enables the composite
    perm: member perm entries shift into the concatenated edge space,
    ``-1`` padding slots stay ``-1``.
    """
    k = len(segs)
    nts = np.array([s.n_tiles for s in segs], np.int64)
    nt_members = int(nts.sum())
    # fresh tail coverage tiles (first segment only)
    n_cov = pad_nodes // T - n_aligned // T if first_segment else 0
    nt = nt_members + n_cov
    nt_bucket = 8
    while nt_bucket < nt:
        nt_bucket *= 2
    # repeat-last-coordinate padding tiles.  A later segment that no member
    # fills still gets the smallest bucket of inert tiles (zero nnz at
    # block-row 0, which the accumulate-mode launch passes through):
    # whether a wave's graphs happen to reach a capacity bucket must not
    # change the composite's leaf shapes, or jit retraces within one
    # padding bucket.  Only an empty composite stays empty.
    n_fill = nt_bucket - nt if nt or not first_segment else 0

    shift = np.repeat(blk_off[:k], nts)  # per-tile block-diagonal offset
    cov_rows = np.arange(n_aligned // T, pad_nodes // T, dtype=np.int64)[:n_cov]
    tile_row = _cat([s.tile_row for s in segs], [cov_rows], np.int64)
    tile_row[:nt_members] += shift
    tile_col = _cat(
        [s.tile_col for s in segs], [np.zeros(n_cov, np.int64)], np.int64
    )
    tile_col[:nt_members] += shift
    last_r = tile_row[nt - 1] if nt else 0
    last_c = tile_col[nt - 1] if nt else 0
    tile_row = np.concatenate([tile_row, np.full(n_fill, last_r)]).astype(np.int32)
    tile_col = np.concatenate([tile_col, np.full(n_fill, last_c)]).astype(np.int32)

    n_pad = n_cov + n_fill
    rows2 = _cat([s.rows for s in segs], [np.zeros((n_pad, cap))], np.int32)
    cols2 = _cat([s.cols for s in segs], [np.zeros((n_pad, cap))], np.int32)
    vals2 = _cat([s.vals for s in segs], [np.zeros((n_pad, cap))], np.float32)
    nnz2 = _cat([s.nnz_in_tile for s in segs], [np.zeros(n_pad)], np.int32)

    perm = None
    if entry_off is not None:
        perm = np.full((nt + n_fill, cap), -1, np.int32)
        if k:
            pstack = np.concatenate([np.asarray(s.perm, np.int64) for s in segs])
            poff = np.repeat(entry_off[:k], nts)[:, None]
            perm[:nt_members] = np.where(
                pstack >= 0, pstack + poff, -1
            ).astype(np.int32)

    return SCVPlan(
        tile_row=tile_row,
        tile_col=tile_col,
        rows=rows2,
        cols=cols2,
        vals=vals2,
        nnz_in_tile=nnz2,
        perm=perm,
        tile=T,
        cap=cap,
        shape=(pad_nodes, pad_nodes),
        order=order,
    )


def _member_segments(g: Graph) -> tuple[SCVPlan, ...]:
    return g.plan.segments if isinstance(g.plan, SCVBucketedPlan) else (g.plan,)


def _assembly_fetches(plans: list[Graph], with_edges: bool) -> int:
    """Member leaves that ``assemble_batched_graph`` reads off a device:
    each segment's tile arrays and, with edges, its perm and the member's
    COO edge arrays.  Zero for host-stage members."""
    read = [
        (s.tile_row, s.tile_col, s.rows, s.cols, s.vals, s.nnz_in_tile)
        + ((s.perm,) if with_edges else ())
        for g in plans
        for s in _member_segments(g)
    ]
    if with_edges:
        read += [(g.rows, g.cols, g.vals) for g in plans]
    return sum(isinstance(x, jax.Array) for x in jax.tree_util.tree_leaves(read))


def assemble_batched_graph(
    plans: list[Graph], tile: int, pad_nodes: int, with_edges: bool = True
) -> BatchedGraph:
    """Fuse prepared per-graph plans into one block-diagonal plan.

    Each member plan already tiles its (tile-padded) own grid, so the
    composite is index arithmetic over the members' plan pytrees: member
    i's tile coordinates shift by ``starts[i] // tile`` and its COO
    rows/cols by ``starts[i]`` — all of it vectorized numpy (concatenate +
    broadcast adds), no per-tile Python loop.  Members are read where
    they are: the engine's host-stage members (``build_host_graph``) in
    place, device-leaf members (``build_graph``) through ``np.asarray``.
    The composite is put to the device once, all leaves in one transfer.
    Member coverage dummies stay valid (each composite block-row belongs
    to exactly one member, so PS block-row contiguity is preserved), and
    the bucket-padding rows at the tail get fresh zero-nnz coverage tiles
    so the Pallas kernel defines the whole output.

    Members carrying nnz-bucketed ``SCVBucketedPlan``s (all on the same
    capacity ladder) compose segment-by-segment — segment j of the
    composite is the fusion of every member's segment j — and the result
    is itself an ``SCVBucketedPlan``; single-cap members compose to a
    single ``SCVPlan`` exactly as before.

    ``with_edges`` controls the composite COO edge arrays + perm: only
    GAT's attention reads them, so non-GAT batches skip both the assembly
    cost and the cache bytes — at the price of a model-kind component in
    the composite cache key (the engine salts it; see ``_batch_plan``).
    """
    T = tile
    k = len(plans)
    bucketed = any(isinstance(g.plan, SCVBucketedPlan) for g in plans)
    if bucketed:
        ladders = {g.plan.caps if isinstance(g.plan, SCVBucketedPlan) else (g.plan.cap,)
                   for g in plans}
        if len(ladders) > 1:
            raise ValueError(
                f"member plans disagree on bucket ladder: {sorted(ladders)}"
            )
        ladder = ladders.pop()
    else:
        caps = {g.plan.cap for g in plans}
        if len(caps) > 1:
            raise ValueError(f"member plans disagree on cap: {sorted(caps)}")
        ladder = (caps.pop() if caps else 8,)
    orders = {g.plan.order for g in plans}
    if len(orders) > 1:
        raise ValueError(f"member plans disagree on order: {sorted(orders)}")
    order = orders.pop() if orders else "zmorton"

    starts = np.zeros(k + 1, np.int64)
    for i, g in enumerate(plans):
        if g.plan.tile != T:
            raise ValueError(f"member plan tiled at {g.plan.tile}, engine at {T}")
        starts[i + 1] = starts[i] + -(-g.n_nodes // T) * T
    n_aligned = int(starts[-1])
    pad_nodes = -(-max(pad_nodes, n_aligned) // T) * T
    blk_off = starts // T

    # --- composite COO edge arrays (GAT re-weighting only) ---
    entry_off = None
    erows = ecols = evals = None
    if with_edges:
        for g in plans:
            if g.rows is None or g.plan.perm is None:
                raise ValueError(
                    "with_edges=True needs member plans built with edges/perm"
                )
        edge_counts = np.array([g.rows.shape[0] for g in plans], np.int64)
        entry_off = np.concatenate([[0], np.cumsum(edge_counts)])
        if entry_off[-1] >= 2**31:  # composite perm is i32
            raise ValueError(
                f"composite entry count {entry_off[-1]} overflows the "
                "int32 perm leaf"
            )
        rows = _cat([g.rows for g in plans], [], np.int64)
        cols = _cat([g.cols for g in plans], [], np.int64)
        eshift = np.repeat(starts[:k], edge_counts)
        erows = (rows + eshift).astype(np.int32)
        ecols = (cols + eshift).astype(np.int32)
        evals = _cat([g.vals for g in plans], [], np.float32)

    composed = [
        _assemble_segment(
            [_member_segments(g)[j] for g in plans],
            blk_off, n_aligned, pad_nodes, T, cap, order, entry_off,
            first_segment=(j == 0),
        )
        for j, cap in enumerate(ladder)
    ]
    plan = SCVBucketedPlan(tuple(composed)) if bucketed else composed[0]
    graph = to_device(
        Graph(n_nodes=pad_nodes, plan=plan, rows=erows, cols=ecols, vals=evals)
    )
    return BatchedGraph(
        graph=graph,
        node_offsets=starts,
        node_counts=np.array([g.n_nodes for g in plans], np.int64),
        n_real_nodes=int(sum(g.n_nodes for g in plans)),
    )


def plan_launches(plan, *, row_gather: bool = False) -> int:
    """Device kernel launches one aggregation over ``plan`` costs.

    A single-cap ``SCVPlan`` is one launch; a bucketed plan chains one
    launch per **non-empty** capacity segment through the aliased
    accumulator (empty segments are skipped at dispatch, and a segment
    longer than ``ops.MAX_LAUNCH_TILES`` tiles takes one launch per span —
    see ``kernels/scv_spmm/ops.scv_spmm_plan``); a sharded plan runs its
    per-segment launches on every mesh instance
    (``tile_parts x feature_parts`` shard_map bodies).  The forward then
    multiplies by ``GNNConfig.n_layers`` — that factor is the caller's
    (every model kind aggregates exactly once per layer).

    ``row_gather=True`` counts only the launches of segments the kernel
    serves with its row-gather body (``core.scv.uses_row_gather``; a
    sharded segment decides by its span width, as its launches do)."""
    segments = getattr(plan, "segments", (plan,))
    if row_gather:
        segments = [
            s for s in segments
            if uses_row_gather(s.cap, s.tile, s.tile_row.shape[-1])
        ]
    if isinstance(plan, ShardedPlan):
        per_device = sum(n_launches(s.tile_row.shape[-1]) for s in segments)
        return per_device * plan.decision.n_devices
    return sum(n_launches(s.tile_row.shape[0]) for s in segments)


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class _TrackedGraph:
    """Current state of a delta-tracked graph: the adjacency after every
    applied delta, and the plan-cache key its plan lives under (the
    delta-chained lineage of the registration-time content key)."""

    adj: COOMatrix
    key: str
    updates_since_anchor: int = 0  # see GraphEngineConfig.anchor_every
    # resolved plan configuration (autotune): set at registration and
    # refreshed at re-anchor time — deltas between anchors may drift the
    # regime, so this is "as of last anchor", which is what metrics()
    # reports per tracked graph
    config: Optional["TunedConfig"] = None


class GraphServeEngine:
    """Drives GNN models over batches of graph requests.

    ``models`` maps a model name to ``(params, GNNConfig)``; requests pick
    a model by name and are batched per model kind (mixed kinds cannot
    share a forward).
    """

    def __init__(
        self,
        models: dict[str, tuple],
        cfg: Optional[GraphEngineConfig] = None,
        executor: Optional["PlanExecutor"] = None,
    ):
        self.models = models
        self.cfg = cfg = cfg if cfg is not None else GraphEngineConfig()
        if executor is None and (
            cfg.shard_nodes_threshold is not None
            or cfg.shard_nnz_threshold is not None
        ):
            from repro.core.exec import PlanExecutor

            executor = PlanExecutor()  # all local devices
        self.executor = executor
        self.plan_cache = PlanCache(
            max_entries=cfg.cache_entries,
            max_bytes=cfg.cache_bytes,
            max_age_s=cfg.plan_ttl_s,
        )
        # intake + wave formation live in the scheduler (the IntakeQueue is
        # the single owner of queued state — scvlint SCV007)
        self.scheduler = Scheduler(self)
        # bounded: a serving process runs forever; retaining every request
        # (adjacency + features + outputs) would grow without limit
        self.completed: deque[GraphRequest] = deque(maxlen=cfg.completed_history)
        self.failed: deque[GraphRequest] = deque(maxlen=cfg.completed_history)
        self.shed: deque[GraphRequest] = deque(maxlen=cfg.completed_history)
        self.n_completed = 0
        self.n_failed = 0
        self.n_rejected = 0  # AdmissionRejected at submit
        self.last_completed: list[GraphRequest] = []  # from the latest run()
        self.n_batches = 0  # composite waves served
        self.n_launches = 0  # actual pallas kernel launches (see plan_launches)
        self.n_row_gather_launches = 0  # of those, row-gather body launches
        self.n_sharded_batches = 0  # waves routed through the executor
        # plan arrays the plan path moves: host->device (the composite's
        # one put) and device->host (a device-leaf member read by assembly)
        self.n_plan_leaf_puts = 0
        self.n_plan_leaf_fetches = 0
        # tuner resolution + resolved-config bookkeeping are shared between
        # the producer thread (submit/registration) and the wave consumer
        self._tune_lock = threading.Lock()
        # delta-tracked graphs (see update()): graph_id -> current state
        self._graphs: dict[str, _TrackedGraph] = {}
        self.n_graph_updates = 0
        # autotuned plan configuration: the engine-config literals become
        # one TunedConfig fallback; with cfg.autotune each regime resolves
        # its own through the tuner's signature-keyed store
        self._fallback_config = TunedConfig(
            tile=cfg.tile, bucket_caps=tuple(cfg.bucket_caps), cap=cfg.cap
        )
        self.tuner = None
        self._resolved_configs: dict[str, TunedConfig] = {}
        if cfg.autotune:
            from repro.tune import Autotuner, TuneStore

            self.tuner = Autotuner(
                store=TuneStore(cfg.autotune_store),
                calibrate=cfg.autotune_calibrate,
            )

    @property
    def queue(self) -> list[GraphRequest]:
        """Read-only snapshot of the queued requests.  Intake is owned by
        the scheduler's ``IntakeQueue`` (bounded, thread-safe); direct
        queue mutation in the serving layer is rejected by scvlint SCV007
        so every path goes through admission accounting."""
        return self.scheduler.queue.items()

    def _resolve_config(self, adj: COOMatrix) -> TunedConfig:
        """The plan configuration a wave uses for ``adj``: the tuner's
        per-regime resolution under ``cfg.autotune``, else the engine-
        config fallback.  Store hits cost one tile-nnz histogram.
        Serialized under ``_tune_lock``: submit-side registration and the
        wave consumer both resolve configs."""
        if self.tuner is None or adj.nnz == 0:
            return self._fallback_config
        with self._tune_lock:
            tcfg = self.tuner.tune(adj)
            self._resolved_configs[self.tuner.last_result.key] = tcfg
        return tcfg

    def _member_content_key(self, adj: COOMatrix) -> str:
        tcfg = self._resolve_config(adj)
        return coo_content_key(adj, tile=tcfg.tile, cap=tcfg.cap_signature)

    def _resolve_adj(self, req: GraphRequest) -> COOMatrix:
        """The adjacency a wave serves for ``req`` — the tracked graph's
        *current* (post-update) state when the request rides a graph_id,
        else the request's own.  Resolved at wave time, never at submit
        time, so an ``update()`` landing between submit and run is
        reflected in the served output."""
        if req.graph_id is not None:
            return self._graphs[req.graph_id].adj
        return req.adj

    def submit(
        self,
        req: GraphRequest,
        *,
        block: bool = True,
        timeout: Optional[float] = None,
    ) -> GraphRequest:
        """Validate and enqueue a request; returns it (callers block on
        ``req.result()`` in async mode).

        Admission control may raise: ``AdmissionRejected`` when the
        request carries a ``deadline_s`` that is infeasible at the current
        queue depth (per-model service-time EMA), and ``EngineOverloaded``
        when the bounded intake queue stays full (with ``block=False`` it
        fails fast; otherwise after ``timeout`` seconds — backpressure
        instead of unbounded queue growth)."""
        with span("serve.submit", rid=req.rid):
            return self._submit(req, block, timeout)

    def _submit(
        self, req: GraphRequest, block: bool, timeout: Optional[float]
    ) -> GraphRequest:
        if req.model not in self.models:
            raise KeyError(f"unknown model {req.model!r}; have {list(self.models)}")
        if req.adj is not None:
            # admission hook (core.validate): squareness, nnz consistency,
            # negative / out-of-range indices, non-finite values.
            # Out-of-range indices would shift into a NEIGHBOR's block of
            # the composite and silently corrupt co-batched outputs.
            check_coo(req.adj, square=True)
            if req.graph_id is not None:
                # (re)register: carrying both adj and graph_id resets the
                # tracked state to this adjacency (content-keyed afresh)
                self._graphs[req.graph_id] = _TrackedGraph(
                    adj=req.adj,
                    key=self._member_content_key(req.adj),
                    config=self._resolve_config(req.adj),
                )
        elif req.graph_id is None:
            raise ValueError("request needs adj (or a tracked graph_id)")
        elif req.graph_id not in self._graphs:
            raise KeyError(
                f"unknown graph_id {req.graph_id!r}; submit once with adj= "
                "to register it"
            )
        adj = self._resolve_adj(req)
        if req.x is None:
            raise ValueError("request needs node features x")
        if req.x.shape[0] != adj.shape[0]:
            raise ValueError(
                f"features rows {req.x.shape[0]} != nodes {adj.shape[0]}"
            )
        # reject malformed width here: inside run() it would crash mid-wave
        # and take the co-batched requests down with it
        _, mcfg = self.models[req.model]
        if req.x.ndim != 2 or req.x.shape[1] != mcfg.d_in:
            raise ValueError(
                f"features shape {req.x.shape} incompatible with model "
                f"{req.model!r} (d_in={mcfg.d_in})"
            )
        req.t_submit = now = time.monotonic()
        if req.event is None:
            req.event = threading.Event()
        try:
            self.scheduler.admit(req, now)
        except AdmissionRejected:
            self.n_rejected += 1
            raise
        if not self.scheduler.queue.put(req, block=block, timeout=timeout):
            raise EngineOverloaded(
                f"intake queue full ({self.cfg.intake_capacity} requests)"
                + (f" after waiting {timeout}s" if timeout is not None else "")
            )
        return req

    def update(self, graph_id: str, delta: DeltaBatch) -> str:
        """Apply an edge delta to a tracked graph; returns its new plan key.

        With the async scheduler loop running, the delta is enqueued as a
        serialized **control message** and applied by the loop *between*
        waves — a mutation can never race a wave that is concurrently
        reading the tracked adjacency or revalidating the plan cache.
        This call blocks until the loop acknowledges, so the caller's
        happens-before is preserved: every request submitted after
        ``update()`` returns serves the post-delta graph.  Without the
        loop it applies inline (the historical synchronous behavior).

        Admission runs ``stream.check_delta`` against the tracked
        adjacency (out-of-range ids, non-finite values, removes of absent
        edges, duplicate/present inserts all rejected before any state
        changes).  The tracked adjacency advances by ``apply_coo`` and the
        plan cache **revalidates by delta**: a live cached plan is patched
        in place via ``stream.apply_delta`` and re-keyed under
        ``delta_key(old, delta)`` (counted in ``stats.revalidated``)
        instead of becoming a full rebuild miss.  Downstream composite and
        sharded cache entries are invalidated automatically: their keys
        combine the member keys, so the re-keyed member can never resolve
        a pre-delta composite — stale entries just age out of the LRU.
        """
        if self.scheduler.running:
            ctrl = _Control(apply=lambda: self._apply_update(graph_id, delta))
            self.scheduler.queue.put_control(ctrl)
            while not ctrl.done.wait(0.05):
                if not self.scheduler.running:
                    # the loop exited between enqueue and apply: drain the
                    # control inline (pop_controls is atomic, so the
                    # message is applied exactly once either way)
                    self.scheduler._apply_controls()
                    break
            if not ctrl.done.is_set():
                self.scheduler._apply_controls()
            if ctrl.error is not None:
                raise ctrl.error
            return ctrl.result
        return self._apply_update(graph_id, delta)

    def _apply_update(self, graph_id: str, delta: DeltaBatch) -> str:
        st = self._graphs.get(graph_id)
        if st is None:
            raise KeyError(
                f"unknown graph_id {graph_id!r}; submit once with adj= to "
                "register it"
            )
        check_delta(delta, coo=st.adj)
        if len(delta) == 0:
            return st.key
        st.adj = apply_coo(st.adj, delta, check=False)
        st.key = self.plan_cache.revalidate(
            st.key, delta, patch=lambda g: apply_delta(g, delta, check=False)
        )
        self.n_graph_updates += 1
        st.updates_since_anchor += 1
        if (
            self.cfg.anchor_every
            and st.updates_since_anchor >= self.cfg.anchor_every
        ):
            # re-home the lineage key to the current adjacency's content
            # key: bounds drift between tracked and content-addressed
            # clients (see PlanCache.anchor)
            st.key = self.plan_cache.anchor(
                st.key, self._member_content_key(st.adj)
            )
            st.config = self._resolve_config(st.adj)
            st.updates_since_anchor = 0
        return st.key

    def tracked_adj(self, graph_id: str) -> COOMatrix:
        """The current adjacency of a tracked graph (post any updates)."""
        st = self._graphs.get(graph_id)
        if st is None:
            raise KeyError(
                f"unknown graph_id {graph_id!r}; submit once with adj= to "
                "register it"
            )
        return st.adj

    # -- plans -------------------------------------------------------------
    def _shard_decision(self, adjs, bucket: int, mcfg):
        """Placement decision for a composite, or None for single-device.

        A composite goes multi-device when its padded node count or total
        nnz exceeds the configured thresholds.  The decision is a pure
        function of (workload numbers, executor pool), so equal batches
        always reach the same placement — which is what lets it live in
        the composite cache key."""
        if self.executor is None:
            return None
        nnz = sum(a.nnz for a in adjs)
        over = (
            self.cfg.shard_nodes_threshold is not None
            and bucket > self.cfg.shard_nodes_threshold
        ) or (
            self.cfg.shard_nnz_threshold is not None
            and nnz > self.cfg.shard_nnz_threshold
        )
        if not over:
            return None
        # the narrowest width any layer aggregates bounds useful Z-sharding
        n_feat = min(mcfg.d_in, mcfg.d_hidden, mcfg.n_classes)
        decision = self.executor.decide_for(nnz, n_feat, n_rows=bucket)
        return None if decision.kind == "replicated" else decision

    def _batch_plan(self, batch: list[GraphRequest]) -> BatchedGraph:
        """Composite plan for a batch.  The composite key is derived from
        content hashes alone, so a hot batch is resolved before any member
        plan is touched — member plans are fetched/built only on a
        composite miss (inside the builder).

        The composite COO edge arrays + perm are assembled lazily: only
        GAT reads them, so the salt carries an ``edges`` component — the
        model-*kind* (edge-needing or not), deliberately not the model
        name, so same-kind models still share composite plans.  Member
        plans always carry edges (one representation serves every kind)
        and stay kind-agnostic.

        The salt also carries the sharding decision (``shard=``): an
        over-threshold composite is cached *placed* (its plan already a
        ``ShardedPlan`` on the executor's mesh), so a hot oversized batch
        reuses its sharded layout with zero placement work — and the same
        members under a different executor/threshold config never alias.

        Delta-tracked members resolve (key, adjacency) from the tracked
        state *here*, at wave time: their member key is the delta-chained
        key ``update()`` maintains, so a post-update wave can never hit a
        pre-delta composite (the composite key combines member keys)."""
        w = batch[0].wave
        with span("serve.plan", wave=w):
            adjs = [self._resolve_adj(r) for r in batch]
            # members were grouped by resolved config at wave formation
            # (Scheduler._pick_wave), so the head's resolution is the layout
            tcfg = self._resolve_config(adjs[0])
            T = tcfg.tile
            _, mcfg = self.models[batch[0].model]
            with_edges = mcfg.kind == "gat"
            # the capacity layout is plan aux: it belongs in both key levels
            # (a single-cap plan and a bucketed plan of the same graph are
            # different device objects)
            cap_sig = tcfg.cap_signature
            with span("serve.plan.key", wave=w):
                member_keys = [
                    self._graphs[r.graph_id].key
                    if r.graph_id is not None
                    else coo_content_key(a, tile=T, cap=cap_sig)
                    for r, a in zip(batch, adjs)
                ]
                aligned = sum(-(-a.shape[0] // T) * T for a in adjs)
                bucket = _bucket_nodes(aligned, self.cfg.node_buckets, T)
                decision = self._shard_decision(adjs, bucket, mcfg)
                ckey = combine_keys(
                    member_keys,
                    salt=f"batch;bucket={bucket};tile={T};caps={cap_sig};"
                    f"edges={int(with_edges)};"
                    f"shard={decision.signature if decision else 'none'};",
                )

            def build_member(a: COOMatrix, rid: int) -> Graph:
                # host stage only: nothing but assembly reads a member
                with span("serve.plan.build", rid=rid):
                    return build_host_graph(a, config=tcfg)

            def build() -> BatchedGraph:
                plans = [
                    self.plan_cache.get_or_build(
                        k, lambda a=a, rid=r.rid: build_member(a, rid)
                    )
                    for k, a, r in zip(member_keys, adjs, batch)
                ]
                self.n_plan_leaf_fetches += _assembly_fetches(plans, with_edges)
                with span("serve.plan.assemble", wave=w):
                    bg = assemble_batched_graph(
                        plans, T, bucket, with_edges=with_edges
                    )
                self.n_plan_leaf_puts += len(jax.tree_util.tree_leaves(bg.graph))
                if decision is not None:
                    bg = dataclasses.replace(
                        bg,
                        graph=self.executor.prepare_graph(
                            bg.graph, decision=decision
                        ),
                    )
                if self.cfg.debug_validate:
                    validate_plan(bg).raise_if_failed()
                return bg

            return self.plan_cache.get_or_build(ckey, build)

    # -- serving -----------------------------------------------------------
    def run(self) -> list[GraphRequest]:
        """Serve every queued request synchronously; returns the newly
        completed ones.  The degenerate single-consumer case of the
        scheduler (waves form with no absorb window — exactly the
        historical snapshot loop).

        A wave that raises re-raises out of run() with its requests either
        requeued (isolated, up to ``max_retries``) or ejected to
        ``self.failed`` — a caller that catches the error and calls run()
        again always makes progress and eventually drains the queue.
        Requests completed before the failing wave are in
        ``self.last_completed`` (and ``self.completed``).  Interrupts
        (BaseExceptions that are not Exceptions, e.g. KeyboardInterrupt)
        restore the wave untouched: they are not request failures and
        consume no retries."""
        if self.scheduler.running:
            raise RuntimeError(
                "the async scheduler loop is running; use wait_idle() to "
                "block on completion or stop() before sync run()"
            )
        return self.scheduler.drain()

    def _dispatch_wave(self, wave: list[GraphRequest]):
        """Assemble a wave's composite and launch the jitted forward;
        returns ``(bg, out)`` with ``out`` **unmaterialized** — jax async
        dispatch returns once the work is enqueued, so the scheduler can
        overlap host-side assembly of the next wave (plan-cache lookups,
        composite concatenation) with this wave's device time."""
        bg, args = self._forward_args(wave)
        with span("serve.dispatch", wave=wave[0].wave):
            return bg, gnn_forward_jit(*args)

    def lower(self, wave: list[GraphRequest]):
        """The jitted forward that serving ``wave`` runs, lowered but not
        run: ``.compile()`` it to time the compile or to read its HLO.
        The wave's composite plan is built and cached as serving would,
        and the forward's jit cache keeps the compiled program."""
        return gnn_forward_jit.lower(*self._forward_args(wave)[1])

    def _forward_args(self, wave: list[GraphRequest]):
        """The wave's composite and the arguments of its jitted forward."""
        bg = self._batch_plan(wave)
        params, mcfg = self.models[wave[0].model]
        with span("serve.features", wave=wave[0].wave):
            x = batch_features(bg, [r.x for r in wave])
        return bg, (params, mcfg, bg.graph, x)

    def _finish_wave(self, wave, bg, out) -> list[GraphRequest]:
        """Materialize a dispatched wave's outputs (blocks on the device),
        complete its requests, and account the wave."""
        w = wave[0].wave
        with span("serve.device_wait", wave=w):
            out.block_until_ready()
        with span("serve.fetch", wave=w):
            host = np.asarray(out)
        with span("serve.split", wave=w):
            outs = split_outputs(bg, host)
            self.n_batches += 1
            if isinstance(bg.graph.plan, ShardedPlan):
                self.n_sharded_batches += 1
            _, mcfg = self.models[wave[0].model]
            # every model kind aggregates once per layer, so a wave costs
            # (launches per aggregation) x n_layers kernel launches
            self.n_launches += plan_launches(bg.graph.plan) * mcfg.n_layers
            self.n_row_gather_launches += (
                plan_launches(bg.graph.plan, row_gather=True) * mcfg.n_layers
            )
            now = time.monotonic()
            done = []
            for r, o in zip(wave, outs):
                r.out = o
                r.done = True
                r.t_done = now
                self.completed.append(r)
                self.n_completed += 1
                if r.t_submit:
                    self.scheduler.record_latency(now - r.t_submit)
                if r.event is not None:
                    r.event.set()
                done.append(r)
            return done

    # -- terminal transitions (called by the scheduler) --------------------
    def _shed_request(self, req: GraphRequest, msg: str) -> None:
        """Deadline shed: admitted under an estimate that later degraded."""
        req.error = msg
        self.shed.append(req)
        if req.event is not None:
            req.event.set()

    def _eject_failed(self, req: GraphRequest, msg: str) -> None:
        """Ejection after ``max_retries`` failed waves."""
        req.error = msg
        self.failed.append(req)
        self.n_failed += 1
        if req.event is not None:
            req.event.set()

    # -- async lifecycle ---------------------------------------------------
    def start(self) -> None:
        """Start the continuous-batching scheduler loop: waves coalesce
        mid-flight and overlap device compute (serve/scheduler.py)."""
        self.scheduler.start()

    def stop(self, timeout: Optional[float] = None, drain: bool = True) -> None:
        """Stop the scheduler loop (draining queued work first by
        default).  Re-raises an interrupt the loop stashed."""
        self.scheduler.stop(timeout=timeout, drain=drain)

    @property
    def running(self) -> bool:
        return self.scheduler.running

    def wait_idle(self, timeout: Optional[float] = None) -> bool:
        """Block until the intake queue is empty and no wave is in flight
        (async mode); returns False on timeout."""
        deadline = (
            time.monotonic() + timeout if timeout is not None else None
        )
        sched = self.scheduler
        while (
            sched.queue.depth()
            or sched.queue.has_controls()
            or sched._inflight
        ):
            if deadline is not None and time.monotonic() > deadline:
                return False
            time.sleep(0.002)
        return True

    def metrics(self) -> dict:
        s = self.plan_cache.stats
        sched = self.scheduler
        lat = sched.latency_percentiles()
        return {
            "batches": self.n_batches,
            "sharded_batches": self.n_sharded_batches,
            # actual pallas kernel launches: a bucketed plan chains one
            # launch per non-empty capacity segment (x mesh shards when
            # sharded) and the forward aggregates once per layer — see
            # plan_launches()
            "launches": self.n_launches,
            # of those, launches of the kernel's row-gather body
            "row_gather_launches": self.n_row_gather_launches,
            "completed": self.n_completed,
            "failed": self.n_failed,
            "shed": sched.n_shed,
            "rejected": self.n_rejected,
            "waves": sched.n_waves,
            "wave_fill": sched.wave_fill,
            "queue_depth": sched.queue.depth(),
            "queue_depth_by_group": sched.queue_depth_by_group(),
            "latency_count": lat["count"],
            "latency_p50_s": lat["p50_s"],
            "latency_p99_s": lat["p99_s"],
            "latency_mean_s": lat["mean_s"],
            "service_ema_s": sched.service_emas(),
            "async_running": sched.running,
            "plan_cache_hits": s.hits,
            "plan_cache_misses": s.misses,
            "plan_cache_evictions": s.evictions,
            "plan_cache_expired": s.expired,
            "plan_cache_revalidated": s.revalidated,
            "plan_cache_anchored": s.anchored,
            "graph_updates": self.n_graph_updates,
            "tracked_graphs": len(self._graphs),
            "plan_cache_bytes": s.bytes_in_use,
            "plan_cache_entries": s.entries,
            "plan_cache_hit_rate": s.hit_rate,
            "plan_build_seconds": s.build_seconds,
            "plan_leaf_puts": self.n_plan_leaf_puts,
            "plan_leaf_fetches": self.n_plan_leaf_fetches,
            # autotune: per-regime resolved configs (key = histogram
            # signature x machine fingerprint) and per tracked graph the
            # config as of its last registration/anchor
            "autotune_enabled": self.tuner is not None,
            "autotune_searches": self.tuner.searches if self.tuner else 0,
            "autotune_cache_hits": self.tuner.cache_hits if self.tuner else 0,
            "resolved_configs": {
                k: c.to_json() for k, c in self._resolved_configs.items()
            },
            "tracked_graph_configs": {
                gid: st.config.to_json()
                for gid, st in self._graphs.items()
                if st.config is not None
            },
        }
