"""The benchmark's harness: one cell, one run.

Everything particular to a configuration, a traffic mix or a metric is
found by name in files of its own:

* ``configs/<config>.json``: the model, its precision, the engine limits
  its deployment fixes, its molecule shapes where it has them, and the
  limit of its output comparison;
* ``traffic/<mix>.json``: the closed loop's concurrency and warm-up, and
  the graph source;
* ``metrics/<metric>.py``: one reader per metric, ``read(run)``, which
  returns a number or ``None`` where it finds nothing to read;
* ``kinds/<kind>.py``: everything that depends on the shape of a model
  kind (the configuration's ``model.kind``), as functions of the
  configuration's ``model``: ``weights(model, key)``, the device pytree
  the program's ``gnn_forward`` takes; ``program_config(model)``,
  ``GNNConfig``'s fields other than ``name`` and ``backend``;
  ``host_weights(params, model)``, what the reference's ``forward`` takes
  as weights; ``model_flops(model, n, nnz)``; and ``aggregations(model,
  n, nnz)``, one ``(width, flops, bytes)`` for each aggregation a forward
  runs, in order;
* the reference a configuration names (``reference.py`` for GCN).

The system under test is ``repro.serve.graph_engine.GraphServeEngine``,
driven through ``submit()`` and its ``start()``/``stop()`` scheduler
loop.  The harness measures from the client's side, on the host clock.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import pathlib
import queue
import sys
import threading
import time
from typing import Optional

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
REPO = ROOT.parent
KINDS = ROOT / "kinds"
#: a request the window waits for after it closes before calling it missing
WAIT_AFTER_CLOSE_S = 60.0


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------
def read_json(path: pathlib.Path) -> dict:
    return json.loads(pathlib.Path(path).read_text())


def benchmark() -> dict:
    return read_json(REPO / "BENCHMARK.json")


def cell_spec(bench: dict, workload: str) -> tuple[dict, dict, dict]:
    """(cell, configuration file, traffic file) of a workload name."""
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = read_json(REPO / entry["file"])
    traffic = read_json(ROOT / "traffic" / f"{cell['traffic']}.json")
    return cell, config, traffic


def metrics_for(bench: dict, workload: str, trace: bool) -> list[dict]:
    """The metrics a run of ``workload`` reports: end-to-end without the
    trace, per-layer with it."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if workload in m.get("workloads", [workload])]


def load_module(path: pathlib.Path):
    """The module in ``path``, loaded once under a name of its own (file
    names may hold dots, and ``trace.py`` shares its name with the
    standard library's module)."""
    name = f"chipbench:{path.relative_to(ROOT)}"
    if name in sys.modules:
        return sys.modules[name]
    if not path.exists():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def trace_module():
    return load_module(ROOT / "trace.py")


def metric_reader(name: str):
    return load_module(ROOT / "metrics" / f"{name}.py")


def kind_module(model: dict):
    """The module of the model's kind, ``kinds/<kind>.py``; an unknown kind
    is an error that names the kinds found."""
    path = KINDS / f"{model['kind']}.py"
    if not path.exists():
        known = sorted(p.stem for p in KINDS.glob("*.py"))
        raise KeyError(f"no model kind {model['kind']!r} in {KINDS}; known: {known}")
    return load_module(path)


# ---------------------------------------------------------------------------
# the deployment: model, weights, engine
# ---------------------------------------------------------------------------
def make_weights(model: dict, seed: int):
    """The kind's weights, made on the device from the seed."""
    import jax

    return kind_module(model).weights(model, jax.random.PRNGKey(derived_seed(seed, "weights")))


def derived_seed(seed: int, what: str) -> int:
    """A 31-bit seed for one purpose, from the run's seed of any size."""
    return int(np.random.default_rng([seed % 2**63, *what.encode()]).integers(2**31))


def build_engine(config: dict, params, full_graph_nodes: Optional[int], backend: str):
    """A ``GraphServeEngine`` with default scheduling and the limits the
    configuration fixes.  A full-graph deployment adds its graph's
    tile-aligned size to the node buckets, so the graph is one bucket and
    not padded to the next power of two."""
    from repro.models.gnn import GNNConfig
    from repro.serve.graph_engine import GraphEngineConfig, GraphServeEngine

    m = config["model"]
    mcfg = GNNConfig(name=config["name"], backend=backend, **kind_module(m).program_config(m))
    kw = dict(config.get("engine", {}))
    if full_graph_nodes is not None:
        defaults = GraphEngineConfig()
        aligned = -(-full_graph_nodes // defaults.tile) * defaults.tile
        kw["node_buckets"] = tuple(defaults.node_buckets) + (aligned,)
        kw["max_batch_nodes"] = max(aligned, defaults.max_batch_nodes)
    return GraphServeEngine({mcfg.name: (params, mcfg)}, GraphEngineConfig(**kw)), mcfg


# ---------------------------------------------------------------------------
# graph sources
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Item:
    """One graph a request can carry: its adjacency and features."""

    adj: object  # repro COOMatrix
    x: np.ndarray
    key: int  # which graph and features, for the reference


class FullGraph:
    """One Table I graph, features cycling over a few seeded matrices."""

    def __init__(self, traffic: dict, config: dict, seed: int):
        import graphs

        from repro.core.formats import COOMatrix

        src = traffic["source"]
        self.coo = graphs.table_graph(src, derived_seed(seed, "graph"))
        c = self.coo
        self.adj = COOMatrix(c.rows, c.cols, c.vals, (c.n, c.n))
        rng = np.random.default_rng(derived_seed(seed, "features"))
        d_in = config["model"]["d_in"]
        self.xs = [
            rng.standard_normal((c.n, d_in), dtype=np.float32)
            for _ in range(int(src["feature_sets"]))
        ]
        self.n_nodes = c.n

    def item(self, i: int) -> Item:
        k = i % len(self.xs)
        return Item(self.adj, self.xs[k], k)

    def graph(self, key: int):
        return self.coo, self.xs[key]


class MoleculeSource:
    """Molecules of the configuration's shape, sent as a stream of
    distinct molecules in order."""

    def __init__(self, traffic: dict, config: dict, seed: int, count: int):
        import graphs

        spec = config["molecules"]
        rng = np.random.default_rng(derived_seed(seed, "molecules"))
        sizes = graphs.stratified_sizes(count, spec, rng)
        self.mols = graphs.molecules(sizes, spec, rng)
        d_in = config["model"]["d_in"]
        erng = np.random.default_rng(derived_seed(seed, "embedding"))
        # the AtomEncoder's stand-in: one embedding per atom type plus one
        # per atom degree, summed
        self.type_emb = erng.standard_normal((len(spec["atom_type_probs"]), d_in), dtype=np.float32)
        self.deg_emb = erng.standard_normal((spec["max_degree"] + 1, d_in), dtype=np.float32)

    def __len__(self) -> int:
        return len(self.mols)

    def features(self, i: int) -> np.ndarray:
        n0, n1 = self.mols.node_off[i], self.mols.node_off[i + 1]
        return self.type_emb[self.mols.atom_type[n0:n1]] + self.deg_emb[self.mols.degree[n0:n1]]

    def item(self, i: int) -> Item:
        from repro.core.formats import COOMatrix

        g = self.mols.graph(i)
        return Item(COOMatrix(g.rows, g.cols, g.vals, (g.n, g.n)), self.features(i), i)

    def graph(self, key: int):
        return self.mols.graph(key), self.features(key)


# ---------------------------------------------------------------------------
# the client
# ---------------------------------------------------------------------------
class _Done(threading.Event):
    """Completion event of one request: the engine sets it on every
    terminal transition; it stamps the time and tells the client."""

    def __init__(self, sink: Optional[queue.SimpleQueue] = None):
        super().__init__()
        self.t: Optional[float] = None
        self.sink = sink
        self.req = None

    def set(self) -> None:
        self.t = time.monotonic()
        super().set()
        if self.sink is not None:
            self.sink.put(self)


@dataclasses.dataclass
class Record:
    """What the client saw of one request."""

    due: float  # when it was sent
    done: Optional[float] = None  # completion time, None if it never completed
    ok: bool = False
    n_nodes: int = 0
    nnz: int = 0
    key: int = 0  # graph/features key for the reference
    out: Optional[np.ndarray] = None
    error: Optional[str] = None


@dataclasses.dataclass
class Window:
    t_open: float
    t_close: float  # t_open + seconds
    t_end: float  # where the rate's window ends (see ``closed_loop``)
    records: list  # Record of every request the window sent
    counters_open: dict
    counters_end: dict

    def due(self) -> list:
        """Requests sent in the window."""
        return [r for r in self.records if self.t_open <= r.due < self.t_close]

    def completed(self) -> list:
        """Requests that completed inside [t_open, t_end]."""
        return [r for r in self.records if r.ok and r.done is not None and r.done <= self.t_end]

    @property
    def seconds(self) -> float:
        return self.t_end - self.t_open


def span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


class Client:
    """Sends requests to a running engine and records what comes back."""

    def __init__(self, engine, model_name: str):
        self.engine = engine
        self.model = model_name
        self.sink: queue.SimpleQueue = queue.SimpleQueue()
        self._rid = 0

    def send(self, item: Item, due: float, notify: bool) -> Record:
        from repro.serve.graph_engine import GraphRequest

        ev = _Done(self.sink if notify else None)
        req = GraphRequest(rid=self._rid, adj=item.adj, x=item.x, model=self.model, event=ev)
        self._rid += 1
        rec = Record(due=due, n_nodes=item.adj.shape[0], nnz=item.adj.nnz, key=item.key)
        ev.req = (req, rec)
        with span("chipbench.submit"):
            try:
                self.engine.submit(req, block=False)
            except Exception as e:  # refused at admission: a missing request
                rec.error = f"{type(e).__name__}: {e}"
                ev.t = time.monotonic()
                if notify:
                    self.sink.put(ev)
                return rec
        return rec

    @staticmethod
    def settle(ev: _Done) -> Record:
        req, rec = ev.req
        if rec.error is None:
            rec.done = ev.t
            rec.ok = req.done and req.error is None
            rec.out = req.out
            rec.error = req.error
        return rec


def counters(engine) -> dict:
    m = engine.metrics()
    keys = ("completed", "waves", "launches", "plan_cache_hits", "plan_cache_misses",
            "plan_build_seconds", "failed", "shed", "rejected")
    return {k: m[k] for k in keys}


def closed_loop(client: Client, items, concurrency: int, seconds: float) -> Window:
    """``concurrency`` requests outstanding: each completion sends the next
    until ``seconds`` have passed.  The window ends at the first
    completion at or after that, so a rate counts whole requests only;
    the requests still outstanding then are drained and checked."""
    counters_open = counters(client.engine)
    records, outstanding = [], 0
    t_open = time.monotonic()
    t_close = t_open + seconds
    nxt = 0
    with span("chipbench.window"):
        for _ in range(concurrency):
            records.append(client.send(items(nxt), time.monotonic(), notify=True))
            nxt += 1
            outstanding += 1
        t_end = None
        while outstanding:
            with span("chipbench.wait"):
                try:
                    ev = client.sink.get(timeout=max(1.0, t_close + WAIT_AFTER_CLOSE_S - time.monotonic()))
                except queue.Empty:
                    break
            client.settle(ev)
            outstanding -= 1
            if t_end is None and ev.t >= t_close:
                t_end = ev.t
                counters_end = counters(client.engine)
            if t_end is None:
                records.append(client.send(items(nxt), time.monotonic(), notify=True))
                nxt += 1
                outstanding += 1
    if t_end is None:  # nothing completed after the close
        t_end, counters_end = time.monotonic(), counters(client.engine)
    return Window(t_open, t_close, t_end, records, counters_open, counters_end)


# ---------------------------------------------------------------------------
# workloads: set-up, warm-up and window of one cell
# ---------------------------------------------------------------------------
class Workload:
    """One cell's traffic over one deployment."""

    def __init__(self, config: dict, traffic: dict, seed: int, seconds: float, backend: str):
        self.config, self.traffic, self.seed, self.seconds = config, traffic, seed, seconds
        src = traffic["source"]
        loop = traffic["loop"]
        if loop["kind"] != "closed":
            raise ValueError(f"unknown loop {loop['kind']!r}: the harness drives closed loops")
        self.rng = np.random.default_rng(derived_seed(seed, "traffic"))
        if src["kind"] == "table_i":
            self.source = FullGraph(traffic, config, seed)
            full_nodes = self.source.n_nodes
        elif src["kind"] == "molecules":
            self.source = MoleculeSource(traffic, config, seed, self._stream_length(src, loop))
            full_nodes = None
        else:
            raise ValueError(f"unknown graph source {src['kind']!r}")
        self.params = make_weights(config["model"], seed)
        self.engine, self.mcfg = build_engine(config, self.params, full_nodes, backend)
        self.client = Client(self.engine, self.mcfg.name)
        self._draws = self._draw_items()

    def _stream_length(self, src: dict, loop: dict) -> int:
        """Molecules a stream needs: its warm-up plus the window at the
        highest rate the traffic file allows for, within the library."""
        seconds = self.seconds + float(loop.get("warmup_seconds", 0.0))
        need = int(loop["warmup_requests"]) + int(seconds * float(src["max_rate_hz"]))
        need += 2 * int(loop.get("concurrency", 0)) + 1
        return min(int(src["library"]), need)

    def _draw_items(self):
        """The sequence of graphs the traffic sends: warm-up first, then
        the window's.  Returns a function of the request's position.  A
        molecule stream leaves out the molecules ``large`` keeps for the
        warm-up, so that every molecule the window sends misses the plan
        cache."""
        if self.traffic["source"]["kind"] == "table_i":
            return self.source.item
        sizes = self.source.mols.sizes()
        above = np.flatnonzero(sizes > self.engine.cfg.tile)
        above = above[np.argsort(sizes[above], kind="stable")]
        # a few molecules of more than one tile's atoms, spread over their sizes
        self.large = np.unique(above[np.linspace(0, len(above) - 1, 8).astype(int)]) if len(above) else []
        stream = np.setdiff1d(np.arange(len(self.source)), self.large)
        return lambda i: self.source.item(int(stream[i % len(stream)]))

    def warm_up(self) -> None:
        """Every shape the window uses, compiled (or read from the compile
        cache) and run, and the caches filled as the window will find them.

        First, waves served synchronously before the scheduler loop
        starts: for a full graph, the graph alone; for molecules, waves of
        every size from one to the engine's wave limit, drawn from the
        warm-up requests with the smallest and largest molecules among
        them.  The fullest waves, which the window forms, are drawn again
        with one to three molecules of more than one tile's atoms in them
        (``large``, kept out of the stream): such a molecule moves the wave
        to the next node bucket and adds tiles to every capacity segment.
        Then the loop starts, and the warm-up requests go through the
        traffic's own loop."""
        loop = self.traffic["loop"]
        n_warm = int(loop["warmup_requests"])
        if self.traffic["source"]["kind"] == "table_i":
            self._serve_wave([self._draws(0)])
        else:
            pool = [self._draws(i) for i in range(n_warm)]
            large = [self.source.item(int(i)) for i in self.large]
            order = np.argsort([it.adj.shape[0] for it in pool], kind="stable")
            spread = order[:: max(1, len(order) // 16)]
            most = self.engine.cfg.max_batch_graphs
            for k in range(1, most + 1):
                mixed = np.concatenate([order[-1:], self.rng.choice(order, k - 1)])
                picks = [order[:k], order[-k:], spread[:k], mixed]
                picks += [self.rng.choice(order, k, replace=k > len(order)) for _ in range(4)]
                waves = [[pool[int(i)] for i in pick] for pick in picks]
                for big in range(1, 4) if large and k > most - 4 else ():
                    waves += [[large[int(j)] for j in self.rng.choice(len(large), big)]
                              + [pool[int(i)] for i in self.rng.choice(order, k - big)]
                              for _ in range(4)]
                for wave in waves:
                    self._serve_wave(wave)
        t = time.monotonic()
        self.engine.start()
        warm = closed_loop(self.client, lambda i: self._draws(n_warm + i),
                           int(loop["concurrency"]), float(loop.get("warmup_seconds", 0.0)))
        self.base = n_warm + len(warm.records)
        if not self.engine.wait_idle(WAIT_AFTER_CLOSE_S):
            raise RuntimeError("warm-up did not finish")
        log(f"warm-up: waves served alone, then {len(warm.records)} requests through the "
            f"loop in {time.monotonic() - t:.3f}s")

    def _serve_wave(self, items: list) -> None:
        """Serve a few requests as one synchronous wave."""
        recs = [self.client.send(it, time.monotonic(), notify=False) for it in items]
        self.engine.run()
        for rec in recs:
            if rec.error:
                raise RuntimeError(f"warm-up request failed: {rec.error}")

    def window(self) -> Window:
        return closed_loop(self.client, lambda i: self._draws(self.base + i),
                           int(self.traffic["loop"]["concurrency"]), self.seconds)

    def stop(self) -> None:
        self.engine.stop(timeout=WAIT_AFTER_CLOSE_S)

    def weights_host(self):
        model = self.config["model"]
        return kind_module(model).host_weights(self.params, model)


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------
def compared(win: Window, workload: Workload, sample: int) -> list:
    """The requests whose answers are compared: every one due in the
    window for a full graph (few, and their references are shared);
    otherwise a sample drawn from the seed, the largest graphs in it."""
    due = win.due()
    if workload.traffic["source"]["kind"] == "table_i" or len(due) <= sample:
        return due
    rng = np.random.default_rng(derived_seed(workload.seed, "sample"))
    largest = sorted(range(len(due)), key=lambda i: -due[i].n_nodes)[:16]
    rest = rng.choice(len(due), size=sample - len(largest), replace=False)
    return [due[i] for i in sorted(set(largest) | set(int(i) for i in rest))]


def reference_module(config: dict):
    return load_module(ROOT / f"{config.get('reference', 'reference')}.py")


def exact(precision: dict) -> dict:
    """The precision of the exact answer: float64 in every stage."""
    return {stage: "float64" for stage in precision}


def answers_gap(records: list, workload: Workload, outs=None) -> float:
    """Widest gap of the served answers of ``records``; with ``outs``, of the
    answers ``outs(weights, adjacency, features)`` gives in their place.

    Each answer is measured against two references, the forward at the
    configuration's stated precision and the exact one, and counts at the
    smaller of its two gaps: an answer may round where the stated
    precision rounds, or not at all.  A missing answer is an infinite gap."""
    return _gaps(records, workload, outs)[0]


def _gaps(records: list, workload: Workload, outs=None) -> tuple[float, list]:
    """``answers_gap``, and the widest gap against each reference alone
    (stated, exact)."""
    ref_mod = reference_module(workload.config)
    weights = workload.weights_host()
    stated = workload.config["precision"]
    worst, each = 0.0, [0.0, 0.0]
    for group in _groups(records, workload):
        adj, xs, spans = _stack(group, workload)
        refs = [ref_mod.forward(weights, adj, xs, p) for p in (stated, exact(stated))]
        got = None if outs is None else outs(weights, adj, xs)
        for rec, (s, e) in zip(group, spans):
            out = rec.out if got is None else got[s:e]
            if out is None:
                return float("inf"), [float("inf")] * 2
            g = [ref_mod.gap(out, ref[s:e]) for ref in refs]
            worst = max(worst, min(g))
            each = [max(a, b) for a, b in zip(each, g)]
    return worst, each


def _groups(records: list, workload: Workload) -> list:
    """Records grouped so that one reference pass serves each group: a
    full graph's records by feature set; molecules in blocks of 256."""
    if workload.traffic["source"]["kind"] == "table_i":
        by_key: dict = {}
        for r in records:
            by_key.setdefault(r.key, []).append(r)
        return list(by_key.values())
    return [records[i:i + 256] for i in range(0, len(records), 256)]


def _stack(group: list, workload: Workload):
    """Block-diagonal adjacency, stacked features, and each record's row
    span, for one reference pass.  Records of one full-graph key share it."""
    ref_mod = reference_module(workload.config)
    if workload.traffic["source"]["kind"] == "table_i":
        coo, x = workload.source.graph(group[0].key)
        adj = ref_mod.adjacency(coo.rows, coo.cols, coo.vals, coo.n)
        return adj, x, [(0, coo.n)] * len(group)
    rows, cols, vals, xs, spans, off = [], [], [], [], [], 0
    for r in group:
        g, x = workload.source.graph(r.key)
        rows.append(g.rows.astype(np.int64) + off)
        cols.append(g.cols.astype(np.int64) + off)
        vals.append(g.vals)
        xs.append(x)
        spans.append((off, off + g.n))
        off += g.n
    adj = ref_mod.adjacency(np.concatenate(rows), np.concatenate(cols), np.concatenate(vals), off)
    return adj, np.concatenate(xs), spans


def check(win: Window, workload: Workload, sample: int = 1024, outs=None) -> dict:
    """The numbers compared, each with its limit, and whether all hold:
    ``missing`` counts requests due in the window that never completed
    (refused, shed, failed or never answered); ``out_gap`` is the widest
    gap of the compared answers against the references (``answers_gap``,
    whose ``outs`` puts other answers in the served ones' place)."""
    due = win.due()
    missing = sum(1 for r in due if not r.ok)
    recs = [r for r in compared(win, workload, sample) if r.ok]
    g, each = _gaps(recs, workload, outs) if recs else (float("inf"), [float("inf")] * 2)
    limit = workload.config["limits"]["out_gap"]
    checks = {
        "missing": {"value": missing, "limit": 0},
        "out_gap": {"value": g, "limit": limit},
        "compared": {"value": len(recs), "limit": 1},
    }
    ok = missing == 0 and g <= limit and len(recs) >= 1
    if not np.isfinite(g):  # no answer to compare: JSON has no infinity
        checks["out_gap"]["value"] = None
    return {"correct": bool(ok), "checks": checks, "vs_stated": each[0], "vs_exact": each[1]}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Run:
    """What metric readers read."""

    cell: dict
    config: dict
    traffic: dict
    setup_s: float
    window: Window
    engine_delta: dict
    peak: dict
    trace: Optional[object] = None  # trace.Reduced of the traced run


def compile_counter():
    """Counts compilations (and compile-cache reads) from now on, and
    their seconds."""
    import jax

    hits = {"n": 0, "s": 0.0}

    def listen(event: str, duration: float, **_) -> None:
        if "backend_compile" in event or "cache_retrieval" in event:
            hits["n"] += 1
            hits["s"] += duration

    jax.monitoring.register_event_duration_secs_listener(listen)
    return hits


@contextlib.contextmanager
def profiled(enabled: bool):
    """A profiler trace around the window, written to a temporary
    directory and reduced before the directory goes."""
    if not enabled:
        yield None
        return
    import tempfile

    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with tempfile.TemporaryDirectory(prefix="chipbench-trace-") as d:
        holder = {"dir": d}
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            yield holder
        finally:
            jax.profiler.stop_trace()
            tr = trace_module()
            t = tr.load(d)
            holder["planes"] = t.planes
            holder["reduced"] = tr.reduce(t)


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def delta(a: dict, b: dict) -> dict:
    return {k: b[k] - a[k] for k in a}


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def execute(cell: dict, config: dict, traffic: dict, metric_specs: list, *, seed: int,
            seconds: float, trace: bool, devices, peak: dict, t_start: float,
            backend: Optional[str] = None) -> dict:
    """Set up, warm up, measure and check one cell; returns the result line.

    ``backend`` overrides the configuration's (the CPU rehearsals run the
    kernel in interpret mode); the chip runs leave it alone."""
    work = Workload(config, traffic, seed, seconds, backend or config["model"]["backend"])
    log(f"set-up: inputs and engine built at {time.monotonic() - t_start:.3f}s")
    work.warm_up()
    compiles = compile_counter()
    with profiled(trace) as prof:
        win = work.window()
    n_compiles, compile_s = compiles["n"], compiles["s"]
    mem = memory_peak(devices)
    work.stop()
    log(f"window: {win.seconds:.3f}s, {len(win.due())} requests due, "
        f"{len(win.completed())} completed in it, {n_compiles} compile(s) inside it "
        f"({compile_s:.3f}s)")
    verdict = check(win, work)
    reduced = prof["reduced"] if prof else None
    if reduced is not None:
        log("trace planes: " + json.dumps(prof["planes"])[:2000])
        if traffic["source"]["kind"] == "table_i":
            import baseline

            baseline.report(work, reduced, len(win.completed()))
    run = Run(cell=cell, config=config, traffic=traffic, setup_s=win.t_open - t_start,
              window=win, engine_delta=delta(win.counters_open, win.counters_end),
              peak=peak, trace=reduced)
    metrics = {}
    for m in metric_specs:
        value = metric_reader(m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind, "count": len(devices),
              "memory_peak_bytes": mem}
    line = {"correct": verdict["correct"], "attempted": len(win.due()),
            "failed": verdict["checks"]["missing"]["value"], "metrics": metrics,
            "device": device}
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        line["breakdown"] = {"device_ops": reduced.device_ops, "idle_gaps": reduced.idle_gaps}
    line["checks"] = verdict["checks"]
    log(f"out_gap against each reference alone: stated {verdict['vs_stated']}, "
        f"exact {verdict['vs_exact']}")
    for name, c in verdict["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    return line
