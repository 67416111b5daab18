"""Plan executor: the one place where SCV plans meet devices (DESIGN.md §5).

Every consumer of an aggregation plan — ``core.aggregate.aggregate``, the
jitted GNN forward (``models.gnn.gnn_forward_jit``) and the serving engine
(``serve.graph_engine``) — dispatches through this module.  The paper's
scalability story (§V-G: equal-nnz Z-Morton spans keep per-device traffic
local; shared PS block-rows merge cheaply) and the feature-parallel axis
the Computing-GNNs taxonomy pairs with it compose here as **one mesh** with
two named axes:

* ``"tiles"``   — graph-parallel: the Z-ordered tile sequence is cut into
  equal-nnz spans (``core.partition.split_equal_nnz``), one span per mesh
  row; boundary PS block-rows are merged with a single ``psum``.
* ``"features"`` — feature-parallel (Z-sharding): each device holds the
  feature slab ``Z[:, f0:f1]``; the kernel's feature-block grid axis maps
  onto this mesh axis (disjoint output columns — no collective at all).

The two axes multiply: a ``(tp, fp)`` mesh runs ``tp * fp`` devices with
one ``psum`` over ``"tiles"`` only.

Three pieces:

* :class:`ShardingDecision` — the placement choice (kind + axis sizes),
  hashable, part of the pytree aux (and therefore of jit trace signatures
  and serving cache keys).
* :class:`ShardedPlan` — a registered pytree holding **per-segment**
  sharded spans: each ``SCVPlan`` segment's leaves carry a leading
  ``tile_parts`` device axis.  Bucketed plans shard segment-by-segment;
  the single ``shard_map`` launch below runs one kernel launch per
  capacity bucket on each device and merges all segments' boundary PS
  rows with **one** ``psum`` (not one per segment).
* :class:`PlanExecutor` — owns the device set and the decision rule
  (``decide_sharding``: tile-span, feature, or 2-D sharding from plan nnz,
  feature width and device count), prepares plans (host-side span split +
  on-device gather), and executes them (``aggregate``).

A prepared :class:`ShardedPlan` is itself just another plan format: it
carries its mesh + decision as static aux, so ``aggregate_scv_plan``
dispatches on it, ``reweighted`` re-gathers per-edge values through the
sharded perm leaves (GAT), and the serving engine caches it — a hot
oversized composite reuses its sharded layout with zero placement work.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.partition import nnz_imbalance, shard_plan, split_equal_nnz
from repro.core.scv import SCVBucketedPlan, SCVPlan

#: Mesh axis names — the executor contract (DESIGN.md §5).
TILE_AXIS = "tiles"
FEATURE_AXIS = "features"

#: Decision-rule floors: sharding an axis must leave each device at least
#: this much work, otherwise collective + padding overhead dominates.
MIN_NNZ_PER_PART = 4096
#: One full kernel feature block (TPU lane width x f32 packing): a slab
#: narrower than 128 columns is padded back up to 128 inside ``scv_spmm``,
#: so splitting below this floor multiplies total work instead of
#: dividing it.
MIN_FEATURES_PER_PART = 128
#: Fallback output-row estimate when the caller only knows nnz: the SCV
#: target regime is sparse power-law graphs with average degree around 8
#: (paper §V datasets), so ``n_rows ~ nnz / 8``.  Pass ``n_rows``
#: explicitly for an exact byte model.
EST_AVG_DEGREE = 8


# ---------------------------------------------------------------------------
# the sharding decision
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ShardingDecision:
    """How a plan meets the mesh.  Hashable: rides in pytree aux (jit trace
    signatures) and in serving cache-key salts (``signature``)."""

    kind: str  # "replicated" | "tiles" | "features" | "2d"
    tile_parts: int = 1
    feature_parts: int = 1

    def __post_init__(self):
        kinds = ("replicated", "tiles", "features", "2d")
        if self.kind not in kinds:
            raise ValueError(f"kind must be one of {kinds}, got {self.kind!r}")
        if self.tile_parts < 1 or self.feature_parts < 1:
            raise ValueError("axis sizes must be >= 1")
        tp, fp = self.tile_parts, self.feature_parts
        ok = {
            "replicated": (tp, fp) == (1, 1),
            "tiles": fp == 1,  # tp == 1 allowed: degenerate 1-span placement
            "features": tp == 1,
            "2d": tp > 1 and fp > 1,
        }[self.kind]
        if not ok:
            raise ValueError(
                f"kind {self.kind!r} inconsistent with axes "
                f"(tile_parts={tp}, feature_parts={fp})"
            )

    @property
    def n_devices(self) -> int:
        return self.tile_parts * self.feature_parts

    @property
    def signature(self) -> str:
        """Stable string for cache-key salts (serving)."""
        return f"{self.kind}:t{self.tile_parts}f{self.feature_parts}"


def placement_bytes(
    nnz: int,
    n_features: int,
    tile_parts: int,
    feature_parts: int,
    *,
    n_rows: Optional[int] = None,
    machine=None,
    n_slots: Optional[int] = None,
) -> dict:
    """Per-device byte model of a ``(tile_parts, feature_parts)`` placement.

    The model charges each device for what it must hold (VMEM residency)
    and move (HBM traffic); ``simul.machine.MachineConfig`` supplies the
    element width and DRAM bandwidth — one shared set of hardware
    constants between the cycle simulator and the executor.

    Resident bytes (what a device's slabs occupy):

    * ``plan``   — the span's COO triples (rows, cols, vals): the tile
      axis splits nnz, so ``3 * nnz * B / tp``; replicated across the
      feature axis.
    * ``z_slab`` — the feature slab ``Z[:, f0:f1]``: split by the feature
      axis, replicated across the tile axis.
    * ``out``    — the output accumulator slab, same split as ``z_slab``
      (every tile span writes the full row range of its feature slab).

    Traffic bytes (what the aggregation streams):

    * ``z_gather``   — the kernel reads one Z row per nonzero entry:
      ``(nnz / tp) * (F / fp) * B``.  This is the dominant sparse term
      and the one the tile axis actually divides; the slab-resident view
      alone would make tile sharding look free-of-benefit.
    * ``collective`` — ring-allreduce traffic of the boundary ``psum``
      over the tile axis: ``2 * (tp - 1) / tp`` of the out slab; zero at
      ``tp == 1`` (the executor skips the psum entirely).

    Returns a dict with those components plus ``resident`` (plan +
    z_slab + out — the VMEM budget number), ``total`` (plan + z_gather +
    out + collective — the cost :func:`decide_sharding` minimizes) and
    ``est_seconds`` (total bits over ``dram_gbps``).  ``n_rows`` defaults
    to ``nnz // EST_AVG_DEGREE`` when the caller only knows nnz.

    ``n_slots`` — the plan's *launched* capacity slots (padding and
    coverage dummies included; ``repro.tune.plan_launched_slots`` of a
    built plan, or ``core.scv.launched_slots`` from a histogram).  When
    given, the plan triple is priced at slots instead of logical nnz —
    the shipped arrays really are slot-shaped, and BENCH_dist measured
    the nnz-priced model 1.11-3.79x optimistic against placed plans.
    This is the same pricing the autotuner's stage-1 model uses
    (``repro.tune.cost``), so placement and plan tuning charge padding
    identically.  Omitted, the legacy nnz pricing applies (callers that
    predate any plan, e.g. the serving admission estimate).
    """
    if machine is None:
        from repro.simul.machine import MachineConfig

        machine = MachineConfig()
    b = machine.bytes_per_elem
    rows = max(int(n_rows) if n_rows is not None else nnz // EST_AVG_DEGREE, 1)
    tp, fp = tile_parts, feature_parts
    if n_slots is None:
        plan = 3 * nnz * b / tp
    else:
        from repro.tune.cost import plan_slot_bytes

        plan = plan_slot_bytes(n_slots, machine) / tp
    z_slab = rows * n_features * b / fp
    out = rows * n_features * b / fp
    z_gather = (nnz / tp) * (n_features / fp) * b
    collective = 2 * (tp - 1) / tp * out
    total = plan + z_gather + out + collective
    return {
        "plan": plan,
        "z_slab": z_slab,
        "out": out,
        "z_gather": z_gather,
        "collective": collective,
        "resident": plan + z_slab + out,
        "total": total,
        "est_seconds": total * 8 / (machine.dram_gbps * 1e9),
    }


def decide_sharding(
    nnz: int,
    n_features: int,
    n_devices: int,
    *,
    n_rows: Optional[int] = None,
    machine=None,
    min_nnz_per_part: int = MIN_NNZ_PER_PART,
    min_features_per_part: int = MIN_FEATURES_PER_PART,
    n_slots: Optional[int] = None,
) -> ShardingDecision:
    """Pick tile-span, feature, or 2-D sharding by byte cost (DESIGN.md §5).

    Candidate meshes are every power-of-two ``(tp, fp)`` with
    ``tp * fp <= n_devices`` that respects the per-device work floors
    (``min_nnz_per_part`` nonzeros per span, ``min_features_per_part``
    columns per slab — splitting below either floor multiplies padded
    work instead of dividing real work).  Each candidate is priced with
    :func:`placement_bytes` and the cheapest per-device byte total wins.

    The model encodes the real trade-off the old grow-tiles-first rule
    missed: the tile axis divides the O(nnz) gather traffic but adds
    ring-allreduce traffic proportional to the out slab, whereas the
    feature axis divides the per-entry width and the slabs collective-
    free.  The optimum balances the two instead of greedily maxing one
    axis — e.g. at nnz=1e6, F=256 on 8 devices the old rule picked
    t8f1 while t4f2 moves ~45% fewer bytes per device.  Ties break
    toward more tile spans (graph parallelism is the paper's lever),
    then toward fewer devices (a half-idle mesh beats all-devices-
    underfed).
    """
    if n_devices < 1:
        raise ValueError("n_devices must be >= 1")
    tps = [1]
    while tps[-1] * 2 <= n_devices and nnz // (tps[-1] * 2) >= min_nnz_per_part:
        tps.append(tps[-1] * 2)
    fps = [1]
    while (
        fps[-1] * 2 <= n_devices
        and n_features // (fps[-1] * 2) >= min_features_per_part
    ):
        fps.append(fps[-1] * 2)
    best = None
    for tp in tps:
        for fp in fps:
            if tp * fp > n_devices:
                continue
            cost = placement_bytes(
                nnz, n_features, tp, fp,
                n_rows=n_rows, machine=machine, n_slots=n_slots,
            )["total"]
            key = (cost, -tp, tp * fp)
            if best is None or key < best[0]:
                best = (key, tp, fp)
    _, tp, fp = best
    kind = (
        "replicated" if (tp, fp) == (1, 1)
        else "tiles" if fp == 1
        else "features" if tp == 1
        else "2d"
    )
    return ShardingDecision(kind=kind, tile_parts=tp, feature_parts=fp)


# ---------------------------------------------------------------------------
# the sharded plan pytree
# ---------------------------------------------------------------------------
@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class ShardedPlan:
    """A plan placed on a mesh: per-segment equal-nnz spans, stacked.

    Leaves: each segment is an :class:`SCVPlan` whose array leaves carry a
    leading ``decision.tile_parts`` device axis (``[tp, span_width, ...]``;
    span-padded slots are zero-nnz tiles, perm slots ``-1``).  Static aux:
    the mesh and the decision — jit specializes on placement exactly like
    it specializes on a plan's ``cap``.

    The generalization of the old ``core.dist.DistributedGraph`` (a plain
    dict of single-cap arrays): bucketed plans shard per segment, and the
    feature axis exists.  ``core.dist`` keeps the old names as aliases.
    """

    segments: tuple[SCVPlan, ...]
    mesh: Mesh
    decision: ShardingDecision

    def tree_flatten(self):
        return (tuple(self.segments),), (self.mesh, self.decision)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(tuple(children[0]), *aux)

    # -- aux delegated to the segments (SCVPlan aux survives sharding) -----
    @property
    def tile(self) -> int:
        return self.segments[0].tile

    @property
    def shape(self) -> tuple[int, int]:
        return self.segments[0].shape

    @property
    def order(self) -> str:
        return self.segments[0].order

    @property
    def caps(self) -> tuple[int, ...]:
        return tuple(s.cap for s in self.segments)

    @property
    def padded_shape(self) -> tuple[int, int]:
        return self.segments[0].padded_shape

    @property
    def n_parts(self) -> int:
        return self.decision.tile_parts

    @property
    def perm(self):
        perms = [s.perm for s in self.segments]
        return None if any(p is None for p in perms) else perms

    def reweighted(self, edge_vals) -> "ShardedPlan":
        """Per-edge re-weighting (GAT) through the sharded perm leaves:
        each span's perm still indexes the *global* edge array (sharding
        gathers tiles, not entries), so the re-gather is unchanged —
        span-padding slots carry ``perm == -1`` and pull the appended
        zero."""
        return dataclasses.replace(
            self, segments=tuple(s.reweighted(edge_vals) for s in self.segments)
        )

    # -- host-side introspection (not part of the trace signature) ---------
    def _segment_nnz_per_part(self, seg: SCVPlan) -> np.ndarray:
        tp = self.decision.tile_parts
        return np.asarray(seg.nnz_in_tile).astype(np.int64).reshape(tp, -1).sum(1)

    def nnz_per_part(self) -> np.ndarray:
        """int64[tile_parts] — nonzeros per device span, summed across
        capacity segments (all segments of one part run on one device)."""
        return sum(
            (self._segment_nnz_per_part(s) for s in self.segments),
            np.zeros(self.decision.tile_parts, np.int64),
        )

    @property
    def imbalance(self) -> float:
        """max/mean nnz over the tile spans (1.0 = perfect balance)."""
        return nnz_imbalance(self.nnz_per_part())

    @property
    def imbalance_per_segment(self) -> tuple[float, ...]:
        """One max/mean ratio per capacity segment (matches
        ``partition.load_imbalance(part, per_segment=True)``)."""
        return tuple(
            nnz_imbalance(self._segment_nnz_per_part(s)) for s in self.segments
        )


# ---------------------------------------------------------------------------
# the sharded aggregation launch
# ---------------------------------------------------------------------------
def _segment_local(seg: SCVPlan) -> SCVPlan:
    """Drop the leading device axis of a span-stacked segment (inside the
    shard_map body each leaf arrives as ``[1, width, ...]``)."""
    return jax.tree.map(lambda a: a[0], seg)


def aggregate_sharded(
    sp: ShardedPlan,
    z: jnp.ndarray,
    *,
    backend: str = "auto",
    feature_block: int = 128,
) -> jnp.ndarray:
    """out = Â Z over a placed plan: ONE ``shard_map`` launch.

    Inside the body each device chains one kernel launch per capacity
    bucket over its tile span through a zero-initialized accumulator
    (``scv_spmm_plan(init="zeros")``): spans carry no per-span coverage
    dummies, and the aliased-accumulator chain leaves unvisited strips at
    their accumulator value — zero — so no post-launch masking and no
    partial-output sum tree.  Boundary PS block-rows merge with a
    **single** ``psum`` over the ``"tiles"`` axis — across all segments,
    not one collective per segment, and skipped entirely when the tile
    axis has one part (pure feature sharding writes disjoint output
    columns and needs no collective at all).  Z is padded to the slab
    grid **once**, outside the mesh body (rows to the tile grid, columns
    to the slab multiple) — per-device per-segment re-padding was two
    full slab copies per call.

    Returns the full (unpadded-row) ``[n_rows, F]`` output, matching
    ``aggregate_scv_plan``.
    """
    from repro.kernels.scv_spmm import ops as scv_ops
    from repro.kernels.scv_spmm import ref as scv_ref

    if backend == "auto":
        backend = "pallas" if jax.default_backend() == "tpu" else "jnp"
    fp = sp.decision.feature_parts
    tp = sp.decision.tile_parts
    n, f = z.shape
    f_pad = -(-f // fp) * fp  # feature slabs must tile the mesh axis
    n_pad = sp.padded_shape[1]  # pad rows once, not per device per segment
    if (n_pad, f_pad) != (n, f):
        z = jnp.zeros((n_pad, f_pad), z.dtype).at[:n, :f].set(z)

    def local(sp_local: ShardedPlan, z_local: jnp.ndarray) -> jnp.ndarray:
        if backend == "jnp":
            out = None
            for seg in sp_local.segments:  # one launch per bucket
                part = scv_ref.scv_spmm_reference_plan(
                    _segment_local(seg), z_local
                )
                out = part if out is None else out + part
        else:
            # chain the per-bucket launches through one accumulator,
            # starting from explicit zeros: a span covers only the rows
            # its tiles visit, and the chain passes unvisited strips
            # through — zero — so the output is defined everywhere
            # without per-span coverage dummies or masking.
            segs = tuple(_segment_local(s) for s in sp_local.segments)
            local_plan = segs[0] if len(segs) == 1 else SCVBucketedPlan(segs)
            out = scv_ops.scv_spmm_plan(
                local_plan, z_local, feature_block=feature_block,
                interpret=(backend == "pallas_interpret"),
                init="zeros",
            )
        if tp == 1:
            return out  # no boundary rows to merge — skip the collective
        return jax.lax.psum(out, TILE_AXIS)  # the §V-G PS merge — once

    specs = jax.tree.map(lambda _: P(TILE_AXIS), sp)
    fn = shard_map(
        local,
        mesh=sp.mesh,
        in_specs=(specs, P(None, FEATURE_AXIS)),
        out_specs=P(None, FEATURE_AXIS),  # psum leaves "tiles" replicated
        # pallas_call has no varying-manual-axes rule: skip the static
        # check there — the psum above makes the output replicated either
        # way; the jnp path keeps the check as a safety net (not at
        # tp == 1, where the psum is skipped and the axis is trivial)
        check_vma=(backend == "jnp" and tp > 1),
    )
    return fn(sp, z)[: sp.shape[0], :f]


# ---------------------------------------------------------------------------
# the executor
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class PlanExecutor:
    """Owns device placement for SCV plans.

    ``devices`` is the flat device pool (defaults to ``jax.devices()`` at
    construction); ``decide`` picks an axis factorization of (a prefix of)
    it, ``prepare`` places a plan, ``aggregate`` executes any plan kind.
    Frozen + hashable so an executor can ride in static argument positions.
    """

    devices: tuple = ()
    min_nnz_per_part: int = MIN_NNZ_PER_PART
    min_features_per_part: int = MIN_FEATURES_PER_PART
    backend: str = "auto"

    def __post_init__(self):
        if not self.devices:
            object.__setattr__(self, "devices", tuple(jax.devices()))

    @property
    def n_devices(self) -> int:
        return len(self.devices)

    def mesh_for(self, decision: ShardingDecision) -> Mesh:
        """(tile_parts, feature_parts) mesh over a prefix of the pool."""
        d = decision.n_devices
        if d > self.n_devices:
            raise ValueError(
                f"decision needs {d} devices, executor has {self.n_devices}"
            )
        grid = np.array(self.devices[:d]).reshape(
            decision.tile_parts, decision.feature_parts
        )
        return Mesh(grid, (TILE_AXIS, FEATURE_AXIS))

    def decide_for(
        self, nnz: int, n_features: int, n_rows: Optional[int] = None,
        n_slots: Optional[int] = None,
    ) -> ShardingDecision:
        """Decision from known workload numbers (the serving engine sums
        member adjacency nnz before any plan exists); ``n_slots`` prices
        the plan triple at launched capacity slots when the caller knows
        the plan layout."""
        return decide_sharding(
            nnz, n_features, self.n_devices,
            n_rows=n_rows,
            min_nnz_per_part=self.min_nnz_per_part,
            min_features_per_part=self.min_features_per_part,
            n_slots=n_slots,
        )

    def decide(
        self, plan: Union[SCVPlan, SCVBucketedPlan], n_features: int
    ) -> ShardingDecision:
        """Decision from a plan's (host-read) nnz + a feature width.

        With a built plan in hand the launched slot count is exact (static
        aux only), so the byte model prices the real padded arrays — the
        autotuner's pricing — rather than the logical-nnz lower bound.
        """
        from repro.tune.cost import plan_launched_slots

        segs = getattr(plan, "segments", (plan,))
        nnz = int(sum(np.asarray(s.nnz_in_tile, np.int64).sum() for s in segs))
        return self.decide_for(
            nnz, n_features, n_rows=plan.shape[0],
            n_slots=plan_launched_slots(plan),
        )

    def prepare(
        self,
        plan: Union[SCVPlan, SCVBucketedPlan],
        n_features: Optional[int] = None,
        decision: Optional[ShardingDecision] = None,
    ) -> Union[SCVPlan, SCVBucketedPlan, ShardedPlan]:
        """Place a plan: equal-nnz span split (host reads the nnz
        histogram once) + on-device span gather, per capacity segment.

        A ``replicated`` decision returns the plan unchanged — single-
        device execution needs no placement.  Pass either ``decision``
        (explicit) or ``n_features`` (let ``decide`` pick).
        """
        if decision is None:
            if n_features is None:
                raise ValueError("prepare needs a decision or n_features")
            decision = self.decide(plan, n_features)
        if decision.kind == "replicated":
            return plan
        mesh = self.mesh_for(decision)
        tp = decision.tile_parts
        part = split_equal_nnz(plan, tp)
        stacked = shard_plan(plan, part)
        segs = getattr(stacked, "segments", (stacked,))
        parts = part if isinstance(part, tuple) else (part,)

        def dev(seg: SCVPlan, p) -> SCVPlan:
            width = p.part_tiles.shape[1]
            seg = jax.tree.map(
                lambda a: a.reshape((tp, width) + a.shape[1:]), seg
            )
            # Span-padding tiles (shard_plan fills coordinates with 0) must
            # repeat the span's LAST real tile coordinates instead: the
            # Pallas kernel zero-initializes a PS strip whenever tile_row
            # changes, so a trailing pad at block-row 0 would wipe the
            # span's real row-0 output (same hazard — and same fix — as
            # the serving assembler's tile-count padding).  An all-pad
            # span keeps row 0: it zero-defines the strip and adds
            # nothing.  nnz == 0 keeps every other leaf inert.
            k = (p.part_tiles >= 0).sum(1)  # real tiles per span (prefix)
            src = np.minimum(np.arange(width)[None, :], np.maximum(k - 1, 0)[:, None])
            src = jnp.asarray(np.where(k[:, None] > 0, src, np.arange(width)[None, :]))
            return dataclasses.replace(
                seg,
                tile_row=jnp.take_along_axis(seg.tile_row, src, axis=1),
                tile_col=jnp.take_along_axis(seg.tile_col, src, axis=1),
            )

        # place each span on its mesh row (replicated along the feature
        # axis): the spans live where the shard_map body reads them, not
        # on the default device awaiting a transfer every call
        span_sharding = NamedSharding(mesh, P(TILE_AXIS))
        return ShardedPlan(
            segments=tuple(
                jax.device_put(dev(s, p), span_sharding)
                for s, p in zip(segs, parts)
            ),
            mesh=mesh,
            decision=decision,
        )

    def aggregate(
        self,
        plan: Union[SCVPlan, SCVBucketedPlan, ShardedPlan],
        z: jnp.ndarray,
        **kw,
    ) -> jnp.ndarray:
        """Execute any plan kind: sharded plans launch the mesh path,
        unplaced plans run single-device (``aggregate_scv_plan``)."""
        kw.setdefault("backend", self.backend)
        if isinstance(plan, ShardedPlan):
            return aggregate_sharded(plan, z, **kw)
        from repro.core.aggregate import aggregate_scv_plan

        return aggregate_scv_plan(plan, z, **kw)

    # -- whole-model convenience (serving + examples) ----------------------
    def prepare_graph(self, g, n_features: Optional[int] = None,
                      decision: Optional[ShardingDecision] = None):
        """Place a ``models.gnn.Graph``'s plan; edge arrays stay replicated
        (GAT's softmax is per-edge host math, tiny next to Z)."""
        placed = self.prepare(g.plan, n_features=n_features, decision=decision)
        if placed is g.plan:
            return g
        return dataclasses.replace(g, plan=placed)
