"""scvcheck leg 2: jit-retrace / trace-hazard analysis.

The serving story depends on a compile-time discipline: the whole GNN
forward runs under ONE ``jax.jit`` and retraces at most once per padding
bucket (``models.gnn.gnn_forward_jit``; the serving engine's node/tile
buckets exist to bound the signature set).  That discipline decays
silently — an unhashable aux object, a weak-typed scalar promoting leaf
dtypes, a float64 constant leaking in, or a new entry point skipping the
plan pytree contract each mint extra traces (or crash at dispatch) with
no test failing until someone counts.

This module turns the hand-written "retraces <= 1 per padding bucket"
test into a reusable analysis any entry point gets for free:

* :func:`check_static_aux` — walk a plan/graph pytree, flag aux data
  that is unhashable (jit dispatch would raise) or array-valued (jit
  would key on object identity and retrace every call).
* :func:`check_leaf_dtypes` — flag float64 leaves (the x64 flag is off:
  a f64 leaf means a host array skipped the f32 conversion and will
  promote everything it touches) and weak-typed leaves (two calls whose
  only difference is weak typing get two traces).
* :func:`eval_shape_hazards` — run a forward under ``jax.eval_shape``
  (no FLOPs, no compile) and flag f64 / weak-type / non-float32 outputs.
* :class:`RetraceCounter` — a jit wrapper whose Python body counts how
  often it is traced; :func:`trace_check` drives it over example graphs
  for each model kind, groups calls by their *expected* trace signature
  (leaf shapes + static aux — i.e. the padding bucket), and reports any
  bucket traced more than ``max_retraces_per_bucket`` times.

Everything reports into a machine-readable :class:`TraceReport`
mirroring ``core.validate.ValidationReport``; ``tests/test_tracecheck.py``
checks both.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import numpy as np


# ---------------------------------------------------------------------------
# report types
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class TraceHazard:
    kind: str  # "unhashable-aux" | "array-aux" | "float64-leak"
    #           | "weak-type" | "bad-output-dtype" | "retrace-bound"
    #           | "trace-error"
    where: str  # pytree path / model name / bucket signature
    detail: str


@dataclasses.dataclass(frozen=True)
class TraceReport:
    """Outcome of :func:`trace_check` (or the standalone checkers)."""

    hazards: tuple[TraceHazard, ...]
    #: ((model, bucket_signature), traces) — one entry per distinct
    #: expected trace signature exercised.
    retraces: tuple[tuple[tuple[str, str], int], ...] = ()

    @property
    def ok(self) -> bool:
        return not self.hazards

    def of_kind(self, kind: str) -> tuple[TraceHazard, ...]:
        return tuple(h for h in self.hazards if h.kind == kind)

    def summary(self) -> str:
        lines = []
        if self.retraces:
            worst = max(n for _, n in self.retraces)
            lines.append(
                f"{len(self.retraces)} trace bucket(s), worst {worst} trace(s)"
            )
        if not self.hazards:
            lines.append("no trace hazards")
        for h in self.hazards:
            lines.append(f"  {h.kind} @ {h.where}: {h.detail}")
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# static aux / leaf hazards
# ---------------------------------------------------------------------------
def _is_arraylike(x: Any) -> bool:
    return isinstance(x, (np.ndarray, jax.Array))


def check_static_aux(tree: Any, where: str = "plan") -> list[TraceHazard]:
    """Flag pytree aux data jit cannot key on.

    Recurses through registered pytree nodes via their own
    ``tree_flatten`` (plans, graphs, sharded plans and the builtin
    containers all qualify).  An aux that fails ``hash()`` makes jit
    dispatch raise; an aux *containing an array* hashes by object
    identity, so every freshly-built plan would retrace even when its
    content is identical.
    """
    out: list[TraceHazard] = []

    def walk(obj: Any, path: str) -> None:
        if _is_arraylike(obj) or obj is None:
            return
        if isinstance(obj, (list, tuple)):
            for i, c in enumerate(obj):
                walk(c, f"{path}[{i}]")
            return
        if isinstance(obj, dict):
            for k, c in obj.items():
                walk(c, f"{path}[{k!r}]")
            return
        if hasattr(obj, "tree_flatten"):
            children, aux = obj.tree_flatten()
            name = type(obj).__name__
            try:
                hash(aux)
            except TypeError as e:
                out.append(
                    TraceHazard(
                        "unhashable-aux", f"{path}:{name}",
                        f"aux data is unhashable ({e}); jit dispatch will "
                        "raise on this pytree",
                    )
                )
            def scan_aux(a: Any, apath: str) -> None:
                if _is_arraylike(a):
                    out.append(
                        TraceHazard(
                            "array-aux", f"{path}:{name}{apath}",
                            "array in static aux: jit keys on object "
                            "identity, so equal plans retrace every build",
                        )
                    )
                elif isinstance(a, (list, tuple)):
                    for i, c in enumerate(a):
                        scan_aux(c, f"{apath}[{i}]")
                elif isinstance(a, dict):
                    for k, c in a.items():
                        scan_aux(c, f"{apath}[{k!r}]")
            scan_aux(aux, ".aux")
            walk(children, path)
            return
        # plain leaf (scalar, string, Mesh, decision dataclass, ...)

    walk(tree, where)
    return out


def check_leaf_dtypes(tree: Any, where: str = "plan") -> list[TraceHazard]:
    """Flag float64 and weak-typed array leaves of a pytree."""
    out: list[TraceHazard] = []
    leaves, _ = jax.tree_util.tree_flatten(tree)
    for i, leaf in enumerate(leaves):
        dt = getattr(leaf, "dtype", None)
        if dt is None:
            continue
        if dt == np.float64:
            out.append(
                TraceHazard(
                    "float64-leak", f"{where}:leaf[{i}]",
                    "float64 leaf (x64 is off — a host array skipped the "
                    "f32 conversion and will promote everything it touches)",
                )
            )
        if getattr(leaf, "weak_type", False):
            out.append(
                TraceHazard(
                    "weak-type", f"{where}:leaf[{i}]",
                    "weak-typed leaf: a strongly-typed twin of the same "
                    "call gets a second trace",
                )
            )
    return out


def eval_shape_hazards(
    fn: Callable, *args, where: str = "forward", **kwargs
) -> list[TraceHazard]:
    """Abstractly evaluate ``fn(*args)`` (``jax.eval_shape`` — no FLOPs,
    no compile) and flag f64 / weak-type / non-float outputs.  Errors
    during abstract evaluation are themselves reported as hazards — a
    forward that cannot even trace is the worst hazard of all."""
    out: list[TraceHazard] = []
    try:
        shapes = jax.eval_shape(fn, *args, **kwargs)
    except Exception as e:  # noqa: BLE001 — any trace failure is the finding
        return [
            TraceHazard(
                "trace-error", where,
                f"{type(e).__name__}: {e}",
            )
        ]
    for i, s in enumerate(jax.tree_util.tree_leaves(shapes)):
        dt = getattr(s, "dtype", None)
        if dt == np.float64:
            out.append(
                TraceHazard(
                    "float64-leak", f"{where}:out[{i}]",
                    "forward output is float64",
                )
            )
        if getattr(s, "weak_type", False):
            out.append(
                TraceHazard(
                    "weak-type", f"{where}:out[{i}]",
                    "forward output is weak-typed",
                )
            )
        if dt is not None and not np.issubdtype(dt, np.floating):
            out.append(
                TraceHazard(
                    "bad-output-dtype", f"{where}:out[{i}]",
                    f"forward output dtype {dt} is not floating",
                )
            )
    return out


# ---------------------------------------------------------------------------
# retrace counting
# ---------------------------------------------------------------------------
class RetraceCounter:
    """``jax.jit`` wrapper whose Python body counts its own traces.

    The wrapped body runs exactly once per distinct jit signature (leaf
    shapes + dtypes + static aux), so ``counter.traces`` is the number of
    XLA programs minted — the quantity the padding buckets bound.
    """

    def __init__(self, fn: Callable, static_argnames=()):
        self.traces = 0

        @functools.wraps(fn)
        def counted(*a, **k):
            self.traces += 1
            return fn(*a, **k)

        self.jitted = jax.jit(counted, static_argnames=static_argnames)

    def __call__(self, *a, **k):
        return self.jitted(*a, **k)


def bucket_signature(*trees: Any) -> str:
    """The *expected* trace signature of a call: leaf shapes + dtypes +
    the treedef (which embeds every static aux repr).  Two calls with
    equal signatures must share one trace — when they don't, something
    (weak types, identity-keyed aux) is minting hidden retraces."""
    leaves, treedef = jax.tree_util.tree_flatten(trees)
    shapes = ";".join(
        f"{getattr(l, 'shape', ())}:{getattr(l, 'dtype', type(l).__name__)}"
        for l in leaves
    )
    return f"{treedef}|{shapes}"


def trace_check(
    models: dict[str, tuple],
    examples: dict[str, list],
    forward: Optional[Callable] = None,
    max_retraces_per_bucket: int = 1,
) -> TraceReport:
    """Run the full trace-hazard analysis over example workloads.

    ``models`` maps a name to ``(params, cfg)`` (the serving engine's
    registry shape); ``examples`` maps the same names to a list of
    ``(graph, x)`` pairs (``models.gnn.Graph`` + feature array).
    ``forward`` defaults to ``models.gnn.gnn_forward``.

    For each model: static-aux and leaf-dtype hazards on every example
    graph, an ``eval_shape`` pass on the first example, then a counted
    jit driven over all examples with calls grouped by
    :func:`bucket_signature`.  Any bucket traced more than
    ``max_retraces_per_bucket`` times becomes a ``retrace-bound`` hazard.
    """
    if forward is None:
        from repro.models.gnn import gnn_forward as forward  # type: ignore

    hazards: list[TraceHazard] = []
    retraces: list[tuple[tuple[str, str], int]] = []
    for name, (params, cfg) in models.items():
        exs = examples.get(name, [])
        if not exs:
            continue
        for i, (g, x) in enumerate(exs):
            hazards += check_static_aux(g, where=f"{name}[{i}]")
            hazards += check_leaf_dtypes((g, x), where=f"{name}[{i}]")
        g0, x0 = exs[0]
        hazards += eval_shape_hazards(
            lambda p, g_, x_: forward(p, cfg, g_, x_),
            params, g0, x0, where=f"{name}:eval_shape",
        )

        counter = RetraceCounter(forward, static_argnames=("cfg",))
        per_bucket: dict[str, int] = {}
        for i, (g, x) in enumerate(exs):
            sig = bucket_signature(g, x)
            before = counter.traces
            try:
                counter(params, cfg, g, x)
            except Exception as e:  # noqa: BLE001 — dispatch failure is the finding
                hazards.append(
                    TraceHazard(
                        "trace-error", f"{name}[{i}]",
                        f"{type(e).__name__}: {e}",
                    )
                )
                continue
            per_bucket[sig] = per_bucket.get(sig, 0) + (counter.traces - before)
        for sig, n in per_bucket.items():
            retraces.append(((name, sig), n))
            if n > max_retraces_per_bucket:
                hazards.append(
                    TraceHazard(
                        "retrace-bound", f"{name}:{sig[:80]}...",
                        f"{n} traces for one padding bucket "
                        f"(bound is {max_retraces_per_bucket}) — equal "
                        "signatures are not sharing a trace",
                    )
                )
    return TraceReport(hazards=tuple(hazards), retraces=tuple(retraces))
