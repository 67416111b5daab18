"""Reduction of a profiler trace to device busy time, kernel time and idle gaps.

The JAX profiler writes ``<dir>/plugins/profile/<time>/*.xplane.pb``.
Device operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane.  Host spans are the events on the host plane
named ``chipbench.*`` (the benchmark's own) or ``serve.*`` (the program's,
``repro.spans``), on the same clock; each keeps the line (the thread) it
was recorded on.

Busy time is the union of the device op intervals inside the window,
averaged over the devices used; idle is the rest of the window.  A
kernel's time is the sum of the durations of its events.  Each long idle
gap is labelled by what the host was doing in it: the program's span
where one runs, else the benchmark's.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from collections import defaultdict

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "chipbench."
PROGRAM_PREFIX = "serve."
WINDOW_SPAN = "chipbench.window"


@dataclasses.dataclass
class Trace:
    """Events as (name, start_ns, end_ns), device ops per device plane."""

    device_ops: dict  # plane name -> list of (name, start, end)
    host_spans: list  # (name, start, end, thread) of the benchmark's and the program's spans
    planes: list  # (plane name, [(line name, n events)]) for the record


def xplane_file(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` (or the newest one under a directory)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = xplane_file(path)
    data = ProfileData.from_file(path)
    device_ops, host_spans, planes = {}, [], []
    for plane in data.planes:
        lines = list(plane.lines)
        planes.append((plane.name, [(ln.name, sum(1 for _ in ln.events)) for ln in lines]))
        if plane.name.startswith("/device:TPU:") and "Core" not in plane.name:
            ops = [
                (ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
                for ln in lines if ln.name == OPS_LINE
                for ev in ln.events
            ]
            device_ops[plane.name] = ops
        elif plane.name.startswith("/host:"):
            host_spans += [
                (ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns), (plane.name, i))
                for i, ln in enumerate(lines)
                for ev in ln.events
                if ev.name.startswith((SPAN_PREFIX, PROGRAM_PREFIX))
            ]
    return Trace(device_ops=device_ops, host_spans=host_spans, planes=planes)


def window(tr: Trace) -> tuple[int, int]:
    """The measured window: the benchmark's ``chipbench.window`` span."""
    spans = [(s, e) for n, s, e, *_ in tr.host_spans if n == WINDOW_SPAN]
    if not spans:
        raise ValueError("trace holds no chipbench.window span")
    return min(s for s, _ in spans), max(e for _, e in spans)


def clip(intervals, lo: int, hi: int) -> list:
    """Intervals cut to [lo, hi], empty ones dropped."""
    out = []
    for iv in intervals:
        s, e = max(iv[-2], lo), min(iv[-1], hi)
        if e > s:
            out.append(iv[:-2] + (s, e))
    return out


def merged(intervals) -> list[tuple[int, int]]:
    """The union of intervals as disjoint sorted (start, end) pairs."""
    out: list[list[int]] = []
    for s, e in sorted((iv[-2], iv[-1]) for iv in intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(intervals, lo: int, hi: int) -> int:
    """Length of the union of the intervals inside [lo, hi]."""
    return sum(e - s for s, e in merged(clip(intervals, lo, hi)))


def gaps(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """The stretches of [lo, hi] that no interval covers."""
    out, t = [], lo
    for s, e in merged(clip(intervals, lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def kernel_ns(ops, name: str, lo: int, hi: int) -> int:
    """Summed durations of the ops of kind ``name`` (see ``op_kind``; an op
    that only reads the kernel's output is not the kernel), in [lo, hi]."""
    return sum(e - s for n, s, e in clip(ops, lo, hi) if op_kind(n) == name)


def op_kind(name: str) -> str:
    """An op's kind from its event name, which on a TPU is the whole HLO
    instruction: ``%scv_spmm.66 = f32[...] custom-call(...)`` is
    ``scv_spmm``, ``%fusion.3 = ...`` is ``fusion``."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    base, _, suffix = head.rpartition(".")
    return base if base and suffix.isdigit() else head


def top_ops(ops, lo: int, hi: int, k: int = 10) -> list:
    """[[op kind, seconds]] of the ``k`` kinds of op that took most time in
    [lo, hi]."""
    total: dict = defaultdict(int)
    for n, s, e in clip(ops, lo, hi):
        total[op_kind(n)] += e - s
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:k]
    return [[n, t / 1e9] for n, t in ranked]


def labelled_gaps(ops, spans, lo: int, hi: int, k: int = 10) -> list:
    """[[label, seconds]] of the ``k`` longest idle gaps in [lo, hi]: what
    the host was doing while the device had nothing to run.  A gap is
    labelled by the program span (``serve.*``) that is innermost on its
    thread for the longest part of it; where none runs in it, by the
    benchmark span that overlaps it most; else "no benchmark span".  A
    span is ``(name, start, end)`` or ``(name, start, end, thread)``;
    spans without a thread count as one thread."""
    longest = sorted(gaps(ops, lo, hi), key=lambda g: g[0] - g[1])[:k]
    program = [sp for sp in spans if sp[0].startswith(PROGRAM_PREFIX)]
    bench = [sp for sp in spans if sp[0].startswith(SPAN_PREFIX) and sp[0] != WINDOW_SPAN]
    out = []
    for gs, ge in longest:
        label = _innermost_most(program, gs, ge) or _overlapping_most(bench, gs, ge)
        out.append([label or "no benchmark span", (ge - gs) / 1e9])
    return out


def _overlapping_most(spans, gs: int, ge: int):
    """The name whose spans overlap [gs, ge] longest, or None."""
    overlap: dict = defaultdict(int)
    for n, s, e, *_ in spans:
        o = min(e, ge) - max(s, gs)
        if o > 0:
            overlap[n] += o
    return max(overlap, key=overlap.get) if overlap else None


def _innermost_most(spans, gs: int, ge: int):
    """The name that is innermost on its thread for the longest part of
    [gs, ge], that time summed over threads, or None.  On one thread the
    innermost of the spans running at an instant is the one that started
    last (the shortest, where two start together): a nested span starts
    after the span that holds it.  Spans of two threads do not nest, so
    each thread names its own at every instant."""
    inside = [(n, s, e, tuple(rest)) for n, s, e, *rest in spans if s < ge and e > gs]
    cuts = sorted({min(max(t, gs), ge) for _, s, e, _ in inside for t in (s, e)})
    held: dict = defaultdict(int)
    for a, b in zip(cuts, cuts[1:]):
        innermost: dict = {}
        for n, s, e, thread in inside:
            if s <= a and b <= e:
                innermost[thread] = max(innermost.get(thread, (s, -e, n)), (s, -e, n))
        for _, _, n in innermost.values():
            held[n] += b - a
    return max(held, key=held.get) if held else None


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float  # mean over devices
    kernel_s: dict  # kernel name -> seconds, summed over devices
    device_ops: list
    idle_gaps: list


def reduce(tr: Trace, kernels=("scv_spmm",)) -> Reduced:
    """Window, busy time, kernel times and the breakdown of one trace."""
    lo, hi = window(tr)
    if not tr.device_ops or not any(tr.device_ops.values()):
        raise ValueError("trace holds no device operations")
    busy = [busy_ns(ops, lo, hi) for ops in tr.device_ops.values()]
    every_op = [op for ops in tr.device_ops.values() for op in ops]
    first = next(iter(tr.device_ops.values()))
    return Reduced(
        window_s=(hi - lo) / 1e9,
        busy_s=sum(busy) / len(busy) / 1e9,
        kernel_s={k: kernel_ns(every_op, k, lo, hi) / 1e9 for k in kernels},
        device_ops=top_ops(every_op, lo, hi),
        idle_gaps=labelled_gaps(first, tr.host_spans, lo, hi),
    )
