"""scvcheck leg 3: repo-specific AST lint rules generic tools can't express.

Rules (all stdlib ``ast`` + ``tokenize``; no third-party dependency):

* **SCV001 np-in-traced** — no ``np.*`` calls inside jitted or Pallas
  kernel bodies.  A numpy call under a trace either crashes on tracers
  or silently constant-folds host-side values into the compiled program.
  "Traced" functions are detected structurally: decorated with
  ``jax.jit`` / ``jax.custom_vjp`` (including ``functools.partial``
  forms), wrapped by a module-level ``name = jax.jit(fn, ...)``,
  registered via ``X.defvjp(fwd, bwd)``, referenced inside a
  ``pallas_call``, or named ``_kernel*`` (the kernel-body idiom in
  ``kernels/scv_spmm``).
* **SCV002 magic-constant** — no literals duplicating the kernel-model
  constants that ``core/scv.py`` owns: the MXU/VPU ratio (``1/16`` /
  ``0.0625`` — import ``MXU_VPU_RATIO``) and chunk-size bindings whose
  name contains ``chunk`` assigned a bare ``128`` (import
  ``DEFAULT_CHUNK``).  Inside ``src/repro/`` the rule further rejects
  re-declared *tunable* plan constants: ``tile`` / ``cap`` bindings or
  parameter defaults with integer literals, and ``bucket_caps`` /
  ``*ladder*`` bindings with literal int tuples — these may only be
  introduced via the ``core/scv.py`` defaults (``DEFAULT_TILE`` /
  ``DEFAULT_CAP`` / ``DEFAULT_LADDER``) or a threaded
  ``repro.tune.TunedConfig`` (``tune/config.py`` is the other exempt
  owner).  Benchmarks and tests sweep candidate values by design and
  stay out of scope.  Drift between the roofline model and the kernel
  is exactly how a "tuned" constant silently stops matching hardware.
* **SCV003 nondiff-plan** — no ``nondiff_argnums`` positions naming
  plan-leaf parameters (``tile_row`` / ``rows`` / ``vals`` / ``perm``
  ...).  Plan leaves arrive as tracers under the end-to-end jitted
  forward; ``nondiff_argnums`` rejects tracers at call time (the PR 3
  regression this rule fossilizes).
* **SCV004 shim-hygiene** — every ``try/except ImportError`` shim whose
  body imports from ``jax`` must carry a version-pin audit comment
  (``# ... jax >= 0.6 ...``) within the preceding 3 lines or the try
  body, so the ROADMAP housekeeping sweep can drop shims by grepping
  pins instead of re-auditing code.
* **SCV005 no-unroll-fori** — no ``unroll=`` keyword on
  ``jax.lax.fori_loop``: jax (0.4.x and current) raises ``ValueError``
  for unrolled loops with traced bounds, and kernel trip counts are
  prefetched data (the PR 2 breakage this rule fossilizes).
* **SCV006 stream-no-rebuild** — no full-rebuild entry points
  (``coo_to_scv_tiles`` / ``plan_from_tiles`` /
  ``plan_from_tiles_bucketed`` / ``build_graph`` and their host stages)
  called inside ``src/repro/stream/``.  The delta package exists to
  *patch* plans in sub-rebuild time; a rebuild call hiding inside it
  silently converts the O(delta) contract back into the O(nnz) path it
  replaces.  Tests and benchmarks rebuild freely — the rule is scoped
  to the package.
* **SCV007 queue-ownership** — no direct ``self.queue`` mutation inside
  ``src/repro/serve/`` outside the scheduler/intake module
  (``serve/scheduler.py``).  The intake queue is the single place where
  admission control, backpressure, and deadline accounting happen; an
  append or slice-assignment that bypasses it silently exempts those
  requests from every admission policy.  Both rebinding
  (``self.queue = ...``, slice/index assignment, ``del``) and mutating
  method calls (``append`` / ``extend`` / ``pop`` / ...) fire.  The
  legacy LM ``serve/engine.py`` loop predates the rule and is
  baselined.

Suppression: append ``# scvlint: ignore[SCV00N]`` (or a bare
``# scvlint: ignore``) to the offending line.  Pre-existing violations
live in ``baseline.txt`` next to this file — matched by (path, rule,
stripped source line) so line-number drift doesn't resurrect them; new
violations fail the run.  Regenerate with ``--write-baseline``.

Run as ``python -m tools.scvlint src/`` (wired into ``scripts/lint.sh``
and ``scripts/ci.sh``).
"""
from __future__ import annotations

import argparse
import ast
import dataclasses
import io
import os
import re
import sys
import tokenize

RULES = {
    "SCV001": "np.* call inside a jitted/Pallas-traced body",
    "SCV002": "literal duplicates a core/scv.py kernel-model constant",
    "SCV003": "nondiff_argnums names a plan-leaf parameter",
    "SCV004": "jax import shim lacks a version-pin audit comment",
    "SCV005": "fori_loop(unroll=) raises with traced bounds",
    "SCV006": "full plan rebuild called inside src/repro/stream/",
    "SCV007": "direct self.queue mutation outside the scheduler/intake module",
}

#: Mutating container methods that bypass intake admission when called on
#: ``self.queue`` directly (SCV007).
QUEUE_MUTATORS = frozenset(
    {"append", "extend", "insert", "pop", "remove", "clear", "sort",
     "reverse", "appendleft", "popleft"}
)

#: Full-rebuild entry points the stream/ delta package must never call
#: (SCV006) — patching that falls back to a rebuild is a silent
#: O(delta) -> O(nnz) regression.
REBUILD_ENTRY_POINTS = frozenset(
    {"coo_to_scv_tiles", "plan_from_tiles", "plan_from_tiles_bucketed",
     "build_graph", "host_plan_from_tiles", "host_plan_from_tiles_bucketed",
     "build_host_graph"}
)

#: SCVPlan / SCVTiles leaf parameter names (SCV003).
PLAN_LEAF_NAMES = frozenset(
    {"tile_row", "tile_col", "rows", "cols", "vals", "nnz_in_tile", "perm",
     "plan", "segments"}
)

_PIN_RE = re.compile(r"jax\s*[<>=!]=?\s*v?\d")
_IGNORE_RE = re.compile(r"#\s*scvlint:\s*ignore(?:\[(?P<rules>[A-Z0-9, ]+)\])?")


@dataclasses.dataclass(frozen=True)
class Violation:
    path: str  # repo-relative, forward slashes
    line: int
    col: int
    rule: str
    message: str
    source_line: str  # stripped — the baseline identity

    def format(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: {self.rule} {self.message}"

    @property
    def baseline_key(self) -> str:
        return f"{self.path}|{self.rule}|{self.source_line}"


# ---------------------------------------------------------------------------
# helpers over the AST
# ---------------------------------------------------------------------------
def _dotted(node: ast.AST) -> str:
    """Best-effort dotted name of an expression (``jax.lax.fori_loop``)."""
    if isinstance(node, ast.Attribute):
        return f"{_dotted(node.value)}.{node.attr}"
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Call):
        return _dotted(node.func)
    return ""


def _names_in(node: ast.AST) -> set[str]:
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


def _collect_traced_functions(tree: ast.Module, rel: str = "") -> set[str]:
    """Names of functions whose bodies run under a jax trace (SCV001)."""
    traced: set[str] = set()
    defvjp_args: set[str] = set()
    jit_wrapped: set[str] = set()
    pallas_refs: set[str] = set()

    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            fn = _dotted(node.func)
            last = fn.rsplit(".", 1)[-1]
            if last == "defvjp":
                for a in node.args:
                    defvjp_args |= _names_in(a)
            # Wrapping sites only: `jax.jit(fn)` / `pl.pallas_call(body)`.
            # A *call of* a jitted function (`foo_jit(...)`) does not drag
            # its arguments under the trace at definition level.
            if last in ("jit", "pallas_call"):
                for a in list(node.args) + [k.value for k in node.keywords]:
                    (pallas_refs if last == "pallas_call" else jit_wrapped).update(
                        _names_in(a)
                    )
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            # The `_kernel*` naming idiom marks Pallas bodies, but only
            # inside the kernels/ tree (host-side code elsewhere may use
            # the prefix).
            if node.name.startswith("_kernel") and "kernels/" in rel:
                traced.add(node.name)
            for dec in node.decorator_list:
                parts = set(_dotted(dec).split("."))
                if parts & {"jit", "custom_vjp"}:
                    traced.add(node.name)
                # functools.partial(jax.jit, ...) / partial(jax.custom_vjp, ...)
                if isinstance(dec, ast.Call):
                    for a in dec.args:
                        if set(_dotted(a).split(".")) & {"jit", "custom_vjp"}:
                            traced.add(node.name)
    return traced | defvjp_args | jit_wrapped | pallas_refs


def _function_defs(tree: ast.Module):
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node


def _positional_params(fn: ast.FunctionDef) -> list[str]:
    return [a.arg for a in fn.args.posonlyargs + fn.args.args]


def _int_literal(node: ast.AST):
    if isinstance(node, ast.Constant) and isinstance(node.value, int) \
            and not isinstance(node.value, bool):
        return node.value
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
        v = _int_literal(node.operand)
        return None if v is None else -v
    return None


# ---------------------------------------------------------------------------
# the checker
# ---------------------------------------------------------------------------
class FileChecker:
    def __init__(self, path: str, rel: str, source: str):
        self.path = path
        self.rel = rel
        self.source = source
        self.lines = source.splitlines()
        self.comments: dict[int, str] = {}
        self.ignores: dict[int, set[str] | None] = {}  # None = all rules
        self._scan_comments()

    def _scan_comments(self) -> None:
        try:
            toks = tokenize.generate_tokens(io.StringIO(self.source).readline)
            for tok in toks:
                if tok.type == tokenize.COMMENT:
                    self.comments[tok.start[0]] = tok.string
                    m = _IGNORE_RE.search(tok.string)
                    if m:
                        rules = m.group("rules")
                        self.ignores[tok.start[0]] = (
                            {r.strip() for r in rules.split(",")} if rules
                            else None
                        )
        except tokenize.TokenError:
            pass

    def _line(self, n: int) -> str:
        return self.lines[n - 1].strip() if 0 < n <= len(self.lines) else ""

    def _emit(self, out: list[Violation], node: ast.AST, rule: str, msg: str):
        line = getattr(node, "lineno", 1)
        ig = self.ignores.get(line, ...)
        if ig is None or (ig is not ... and rule in ig):
            return
        out.append(
            Violation(
                path=self.rel, line=line,
                col=getattr(node, "col_offset", 0) + 1,
                rule=rule, message=msg, source_line=self._line(line),
            )
        )

    def check(self) -> list[Violation]:
        try:
            tree = ast.parse(self.source, filename=self.path)
        except SyntaxError as e:
            return [
                Violation(
                    path=self.rel, line=e.lineno or 1, col=e.offset or 1,
                    rule="SCV000", message=f"syntax error: {e.msg}",
                    source_line=self._line(e.lineno or 1),
                )
            ]
        out: list[Violation] = []
        self._check_np_in_traced(tree, out)
        self._check_magic_constants(tree, out)
        self._check_nondiff_plan(tree, out)
        self._check_shim_hygiene(tree, out)
        self._check_fori_unroll(tree, out)
        self._check_stream_no_rebuild(tree, out)
        self._check_queue_ownership(tree, out)
        return out

    # -- SCV001 ------------------------------------------------------------
    def _check_np_in_traced(self, tree: ast.Module, out: list[Violation]):
        traced = _collect_traced_functions(tree, self.rel.replace("\\", "/"))
        for fn in _function_defs(tree):
            if fn.name not in traced:
                continue
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                d = _dotted(node.func)
                if d.startswith(("np.", "numpy.")):
                    self._emit(
                        out, node, "SCV001",
                        f"`{d}` called inside traced body `{fn.name}` — "
                        "use jnp/lax, or mark a deliberate host-side "
                        "constant with `# scvlint: ignore[SCV001]`",
                    )

    # -- SCV002 ------------------------------------------------------------
    def _check_magic_constants(self, tree: ast.Module, out: list[Violation]):
        rel = self.rel.replace("\\", "/")
        if rel.endswith(("core/scv.py", "tune/config.py")):
            return  # the owners of the constants
        # The tunable plan constants (tile / cap / ladder) are policed
        # inside src/repro/ only: benchmarks and tests sweep candidate
        # values by design.
        tunable_scope = "src/repro/" in rel or rel.startswith("repro/")
        for node in ast.walk(tree):
            # 1/16 or 1.0/16.0 → MXU_VPU_RATIO
            if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Div):
                lv = getattr(node.left, "value", None)
                rv = getattr(node.right, "value", None)
                if lv in (1, 1.0) and rv in (16, 16.0):
                    self._emit(
                        out, node, "SCV002",
                        "`1/16` duplicates core.scv.MXU_VPU_RATIO — import it",
                    )
            if isinstance(node, ast.Constant) and node.value == 1 / 16:  # scvlint: ignore[SCV002]
                self._emit(
                    out, node, "SCV002",
                    "`0.0625` duplicates core.scv.MXU_VPU_RATIO — import it",
                )
            # <name containing 'chunk'> = 128 → DEFAULT_CHUNK
            targets: list[tuple[str, ast.AST]] = []
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        targets.append((t.id, node.value))
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                if isinstance(node.target, ast.Name):
                    targets.append((node.target.id, node.value))
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                args = node.args
                pos = args.posonlyargs + args.args
                for a, dflt in zip(pos[len(pos) - len(args.defaults):],
                                   args.defaults):
                    targets.append((a.arg, dflt))
                for a, dflt in zip(args.kwonlyargs, args.kw_defaults):
                    if dflt is not None:
                        targets.append((a.arg, dflt))
            for name, value in targets:
                low = name.lower()
                if "chunk" in low and _int_literal(value) == 128:
                    self._emit(
                        out, value, "SCV002",
                        f"`{name} = 128` duplicates core.scv.DEFAULT_CHUNK — "
                        "import it",
                    )
                if not tunable_scope:
                    continue
                # tunable plan constants may only be introduced through
                # core/scv.py defaults or a threaded TunedConfig — a
                # re-declared literal is exactly the drift the autotuner
                # exists to eliminate
                if low in ("tile", "cap") and _int_literal(value) is not None:
                    self._emit(
                        out, value, "SCV002",
                        f"`{name} = {_int_literal(value)}` re-declares a "
                        f"tunable plan constant — import "
                        f"core.scv.DEFAULT_{low.upper()} or thread a "
                        "repro.tune.TunedConfig",
                    )
                if ("bucket_caps" in low or "ladder" in low) and isinstance(
                    value, (ast.Tuple, ast.List)
                ):
                    if value.elts and all(
                        _int_literal(e) is not None for e in value.elts
                    ):
                        self._emit(
                            out, value, "SCV002",
                            f"`{name} = (...)` re-declares a capacity "
                            "ladder — import core.scv.DEFAULT_LADDER or "
                            "thread a repro.tune.TunedConfig",
                        )

    # -- SCV003 ------------------------------------------------------------
    def _check_nondiff_plan(self, tree: ast.Module, out: list[Violation]):
        for fn in _function_defs(tree):
            for dec in fn.decorator_list:
                if not isinstance(dec, ast.Call):
                    continue
                d = _dotted(dec)
                inner = " ".join(_dotted(a) for a in dec.args)
                if "custom_vjp" not in d and "custom_vjp" not in inner:
                    continue
                for kw in dec.keywords:
                    if kw.arg != "nondiff_argnums":
                        continue
                    nums = []
                    if isinstance(kw.value, (ast.Tuple, ast.List)):
                        nums = [
                            v for v in
                            (_int_literal(e) for e in kw.value.elts)
                            if v is not None
                        ]
                    else:
                        v = _int_literal(kw.value)
                        nums = [v] if v is not None else []
                    params = _positional_params(fn)
                    bad = [
                        params[i] for i in nums
                        if 0 <= i < len(params) and params[i] in PLAN_LEAF_NAMES
                    ]
                    if bad:
                        self._emit(
                            out, dec, "SCV003",
                            f"nondiff_argnums marks plan leaf param(s) "
                            f"{bad} on `{fn.name}` — plan leaves arrive as "
                            "tracers under the jitted forward; carry them "
                            "as residuals with float0 cotangents instead",
                        )

    # -- SCV004 ------------------------------------------------------------
    def _check_shim_hygiene(self, tree: ast.Module, out: list[Violation]):
        for node in ast.walk(tree):
            if not isinstance(node, ast.Try):
                continue
            catches_import = any(
                "ImportError" in _names_in(h.type) if h.type is not None else False
                for h in node.handlers
            ) or any(
                isinstance(h.type, ast.Attribute) and h.type.attr == "ImportError"
                for h in node.handlers if h.type is not None
            )
            if not catches_import:
                continue
            imports_jax = any(
                isinstance(s, (ast.Import, ast.ImportFrom))
                and any(
                    (getattr(s, "module", None) or "").split(".")[0] == "jax"
                    or (isinstance(s, ast.Import)
                        and any(a.name.split(".")[0] == "jax" for a in s.names))
                    for _ in (0,)
                )
                for s in node.body
            )
            if not imports_jax:
                continue
            lo = max(1, node.lineno - 3)
            hi = max(
                (getattr(s, "end_lineno", s.lineno) for s in node.body),
                default=node.lineno,
            )
            pinned = any(
                _PIN_RE.search(self.comments.get(ln, ""))
                for ln in range(lo, hi + 1)
            )
            if not pinned:
                self._emit(
                    out, node, "SCV004",
                    "jax import shim without a version-pin audit comment — "
                    "add e.g. `# jax >= 0.6 re-homes X; drop the except "
                    "branch once the image moves` near the try",
                )

    # -- SCV005 ------------------------------------------------------------
    def _check_fori_unroll(self, tree: ast.Module, out: list[Violation]):
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and _dotted(node.func).endswith(
                "fori_loop"
            ):
                for kw in node.keywords:
                    if kw.arg == "unroll":
                        self._emit(
                            out, node, "SCV005",
                            "fori_loop(unroll=) raises ValueError with "
                            "traced bounds (jax 0.4.x and current); kernel "
                            "trip counts are prefetched data — drop it",
                        )

    # -- SCV006 ------------------------------------------------------------
    def _check_stream_no_rebuild(self, tree: ast.Module, out: list[Violation]):
        rel = self.rel.replace("\\", "/")
        if "repro/stream/" not in rel:
            return
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            last = _dotted(node.func).rsplit(".", 1)[-1]
            if last in REBUILD_ENTRY_POINTS:
                self._emit(
                    out, node, "SCV006",
                    f"`{last}` is a full O(nnz) plan rebuild — stream/ "
                    "patches plans in O(delta); splice the change in "
                    "instead of rebuilding",
                )

    # -- SCV007 ------------------------------------------------------------
    def _check_queue_ownership(self, tree: ast.Module, out: list[Violation]):
        rel = self.rel.replace("\\", "/")
        if "repro/serve/" not in rel or rel.endswith("serve/scheduler.py"):
            return

        def root_is_self_queue(node: ast.AST) -> bool:
            # peel subscripts: `self.queue[0] = ...`, `del self.queue[:]`
            while isinstance(node, ast.Subscript):
                node = node.value
            return (
                isinstance(node, ast.Attribute)
                and node.attr == "queue"
                and isinstance(node.value, ast.Name)
                and node.value.id == "self"
            )

        msg = (
            "direct `self.queue` mutation bypasses intake admission "
            "(backpressure, deadline accounting) — go through "
            "serve.scheduler.IntakeQueue"
        )
        for node in ast.walk(tree):
            targets: list[ast.AST] = []
            if isinstance(node, ast.Assign):
                targets = list(node.targets)
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                targets = [node.target]
            elif isinstance(node, ast.Delete):
                targets = list(node.targets)
            for t in targets:
                subs = (
                    list(ast.walk(t))
                    if isinstance(t, (ast.Tuple, ast.List)) else [t]
                )
                if any(root_is_self_queue(s) for s in subs):
                    self._emit(out, node, "SCV007", msg)
                    break
            if (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in QUEUE_MUTATORS
                and root_is_self_queue(node.func.value)
            ):
                self._emit(out, node, "SCV007", msg)


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------
def _iter_py_files(paths: list[str]):
    for p in paths:
        if os.path.isfile(p) and p.endswith(".py"):
            yield p
        elif os.path.isdir(p):
            for root, dirs, files in os.walk(p):
                dirs[:] = [d for d in dirs if d not in
                           ("__pycache__", ".git", ".venv", "node_modules")]
                for f in sorted(files):
                    if f.endswith(".py"):
                        yield os.path.join(root, f)


def check_paths(paths: list[str], repo_root: str | None = None) -> list[Violation]:
    root = os.path.abspath(repo_root or os.getcwd())
    out: list[Violation] = []
    for path in _iter_py_files(paths):
        rel = os.path.relpath(os.path.abspath(path), root).replace(os.sep, "/")
        try:
            with open(path, encoding="utf-8") as f:
                source = f.read()
        except (OSError, UnicodeDecodeError) as e:
            print(f"scvlint: cannot read {path}: {e}", file=sys.stderr)
            continue
        out.extend(FileChecker(path, rel, source).check())
    return sorted(out, key=lambda v: (v.path, v.line, v.col, v.rule))


def check_source(source: str, rel: str = "<string>") -> list[Violation]:
    """Lint a source string (the unit-test entry point)."""
    return FileChecker(rel, rel, source).check()


DEFAULT_BASELINE = os.path.join(os.path.dirname(__file__), "baseline.txt")


def load_baseline(path: str) -> set[str]:
    if not os.path.exists(path):
        return set()
    with open(path, encoding="utf-8") as f:
        return {
            line.rstrip("\n") for line in f
            if line.strip() and not line.startswith("#")
        }


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="scvlint",
        description="SCV-GNN repo-specific lint (see tools/scvlint).",
    )
    ap.add_argument("paths", nargs="*", default=["src"])
    ap.add_argument("--baseline", default=DEFAULT_BASELINE)
    ap.add_argument(
        "--no-baseline", action="store_true",
        help="report every violation, including baselined ones",
    )
    ap.add_argument(
        "--write-baseline", action="store_true",
        help="accept current violations as the new baseline",
    )
    args = ap.parse_args(argv)

    violations = check_paths(args.paths or ["src"])
    if args.write_baseline:
        os.makedirs(os.path.dirname(args.baseline), exist_ok=True)
        with open(args.baseline, "w", encoding="utf-8") as f:
            f.write(
                "# scvlint baseline — pre-existing violations that do not\n"
                "# fail the run.  One `path|rule|stripped source line` per\n"
                "# entry; regenerate with `python -m tools.scvlint "
                "--write-baseline`.\n"
            )
            for key in sorted({v.baseline_key for v in violations}):
                f.write(key + "\n")
        print(f"scvlint: wrote {len(violations)} violation(s) to {args.baseline}")
        return 0

    baseline = set() if args.no_baseline else load_baseline(args.baseline)
    fresh = [v for v in violations if v.baseline_key not in baseline]
    old = len(violations) - len(fresh)
    for v in fresh:
        print(v.format())
    if fresh:
        print(
            f"scvlint: {len(fresh)} new violation(s)"
            + (f" ({old} baselined)" if old else "")
        )
        return 1
    print(
        "scvlint: clean"
        + (f" ({old} baselined violation(s) tolerated)" if old else "")
        + f" — checked {len(RULES)} rule(s)"
    )
    return 0
