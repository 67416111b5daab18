"""Graph builders of the benchmark, made from a seed.

Two kinds of input graph, both as plain numpy COO arrays (int32 rows and
cols, float32 values) of the GCN-normalised adjacency
``D^-1/2 (A + I) D^-1/2``:

* ``chung_lu``: the power-law graphs of the paper's Table I, drawn the
  way the program's ``simul.datasets.load`` draws them (Chung-Lu with
  Zipf weights, alpha 2.1, deduplicated directed edges, self loops added
  by the normalisation).  A copy, so that the yardstick stays fixed when
  the program changes.  ``table_graph`` draws one per seed, or one per
  dataset that each seed renames block by block (``relabel_blocks``).
* ``molecules``: molecule-shaped graphs in bulk.  Each is a random tree
  of atoms with degree at most 4, closed into 5- and 6-rings, stored
  undirected (both directions) with self loops.  All molecules of a set
  live in one flat ``MoleculeSet`` and are sliced per request.

Nothing here imports the program.
"""
from __future__ import annotations

import dataclasses
import zlib

import numpy as np


@dataclasses.dataclass(frozen=True)
class Coo:
    rows: np.ndarray  # int32[nnz]
    cols: np.ndarray  # int32[nnz]
    vals: np.ndarray  # float32[nnz]
    n: int

    @property
    def nnz(self) -> int:
        return int(self.rows.shape[0])


def name_seed(name: str) -> int:
    """Per-dataset seed offset, a CRC of the name (stable across processes)."""
    return zlib.crc32(name.encode()) % 2**16


def gcn_normalize(rows, cols, vals, n: int) -> Coo:
    """``D^-1/2 (A + I) D^-1/2`` with the self loops appended after ``A``."""
    rows = np.concatenate([rows, np.arange(n, dtype=np.int32)]).astype(np.int32)
    cols = np.concatenate([cols, np.arange(n, dtype=np.int32)]).astype(np.int32)
    vals = np.concatenate([vals, np.ones(n, np.float32)]).astype(np.float32)
    deg = np.bincount(rows, weights=vals.astype(np.float64), minlength=n)
    dinv = 1.0 / np.sqrt(np.maximum(deg, 1e-12))
    w = (dinv[rows] * vals * dinv[cols]).astype(np.float32)
    return Coo(rows, cols, w, n)


def chung_lu(nodes: int, edges: int, seed: int, alpha: float = 2.1) -> Coo:
    """Chung-Lu power-law graph, ``P(u -> v) ~ w_u w_v`` with Zipf weights,
    GCN-normalised: the draw of the program's ``powerlaw_graph``, except
    that it draws again until it holds ``edges`` distinct edges (that one
    keeps what the first overdraw gives, 18% short on CoBuy Computer)."""
    n, m = nodes, edges
    rng = np.random.default_rng(seed)
    w = np.arange(1, n + 1, dtype=np.float64) ** (-1.0 / (alpha - 1.0))
    rng.shuffle(w)
    p = w / w.sum()
    key = np.zeros(0, np.int64)
    draw = int(m * 1.15) + 16
    while len(key) < m:
        src = rng.choice(n, size=draw, p=p)
        dst = rng.choice(n, size=draw, p=p)
        key = np.unique(np.concatenate([key, src.astype(np.int64) * n + dst]))
        draw = 2 * (m - len(key)) + 16
    rng.shuffle(key)
    key = key[:m]
    rows = (key // n).astype(np.int32)
    cols = (key % n).astype(np.int32)
    return gcn_normalize(rows, cols, np.ones(len(key), np.float32), n)


def table_graph(spec: dict, seed: int) -> Coo:
    """A Table I graph at ``spec["scale"]`` of its published size.

    Drawn from ``seed``; or, where ``spec`` has ``"block"``, drawn once
    from the dataset's name and relabelled by ``seed`` (``relabel_blocks``),
    so that every seed serves the same tiles in another order."""
    scale = float(spec.get("scale", 1.0))
    nodes = max(64, int(spec["nodes"] * scale))
    edges = max(256, int(spec["edges"] * scale))
    if "block" in spec:
        g = chung_lu(nodes, edges, name_seed(spec["name"]))
        return relabel_blocks(g, int(spec["block"]), np.random.default_rng(seed))
    return chung_lu(nodes, edges, seed + name_seed(spec["name"]))


def relabel_blocks(g: Coo, block: int, rng: np.random.Generator) -> Coo:
    """``g`` with its nodes renamed: the whole blocks of ``block`` nodes
    in a random order, and the nodes of each block shuffled within it
    (the last, partial block stays last).  Every ``block`` x ``block``
    tile keeps its entries, so the graph's tile histogram, and with it
    the work of a tiled aggregation, does not change."""
    n = g.n
    full = n // block
    new = np.empty(n, np.int64)
    dest = rng.permutation(full)
    within = rng.permuted(np.tile(np.arange(block), (full, 1)), axis=1)
    new[: full * block] = (dest[:, None] * block + within).ravel()
    new[full * block:] = rng.permutation(np.arange(full * block, n))
    return Coo(new[g.rows].astype(np.int32), new[g.cols].astype(np.int32), g.vals, n)


# ---------------------------------------------------------------------------
# molecules
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class MoleculeSet:
    """Many molecules, flat.  Molecule i owns nodes
    ``node_off[i]:node_off[i+1]`` (its atom types and degrees) and COO
    entries ``entry_off[i]:entry_off[i+1]`` with molecule-local indices."""

    node_off: np.ndarray  # int64[M+1]
    entry_off: np.ndarray  # int64[M+1]
    rows: np.ndarray  # int32[E], local
    cols: np.ndarray  # int32[E], local
    vals: np.ndarray  # float32[E]
    atom_type: np.ndarray  # int32[N]
    degree: np.ndarray  # int32[N], bonds per atom

    def __len__(self) -> int:
        return len(self.node_off) - 1

    def sizes(self) -> np.ndarray:
        return np.diff(self.node_off)

    def bonds(self) -> np.ndarray:
        return (np.diff(self.entry_off) - self.sizes()) // 2

    def graph(self, i: int) -> Coo:
        e0, e1 = self.entry_off[i], self.entry_off[i + 1]
        n = int(self.node_off[i + 1] - self.node_off[i])
        return Coo(self.rows[e0:e1], self.cols[e0:e1], self.vals[e0:e1], n)


def stratified_sizes(count: int, spec: dict, rng: np.random.Generator) -> np.ndarray:
    """``count`` atom counts whose histogram follows a gamma distribution of
    ``spec``'s mean and spread, rounded to whole atoms and clipped, by
    largest remainders, in a seeded order.  Every seed gets the same
    multiset of sizes, so the work does not change with the seed."""
    mean, sd = float(spec["mean_atoms"]), float(spec["sd_atoms"])
    lo, hi = int(spec["min_atoms"]), int(spec["max_atoms"])
    k, theta = (mean / sd) ** 2, sd * sd / mean
    s = np.arange(lo, hi + 1, dtype=np.float64)
    x = np.linspace(1e-9, hi + 0.5, 200_001)
    logpdf = (k - 1) * np.log(x) - x / theta
    cdf = np.cumsum(np.exp(logpdf - logpdf.max()))
    cdf /= cdf[-1]
    edges = np.interp(np.concatenate([[lo - 0.5], s + 0.5]), x, cdf)
    edges[0], edges[-1] = 0.0, 1.0  # the clipped tails join the end sizes
    share = np.diff(edges) * count
    n_each = np.floor(share).astype(np.int64)
    rest = count - int(n_each.sum())
    n_each[np.argsort(n_each - share, kind="stable")[:rest]] += 1
    return rng.permutation(np.repeat(s.astype(np.int64), n_each))


def molecules(sizes: np.ndarray, spec: dict, rng: np.random.Generator) -> MoleculeSet:
    """Molecule graphs of the given atom counts.

    Atoms join one at a time: atom ``l`` bonds to atom ``l - 1`` with
    probability ``chain_prob`` and otherwise to a uniformly drawn earlier
    atom, or to ``l - 1`` where the drawn one already has ``max_degree``
    bonds, so the tree keeps the valence bound.  Ring
    closures then bond an atom to its 4th or 5th ancestor (a 5- or
    6-ring) where both ends have room; their rate is set so that the mean
    bond count per molecule is ``spec["mean_bonds"]``.
    """
    sizes = np.asarray(sizes, np.int64)
    M = len(sizes)
    max_deg = int(spec["max_degree"])
    node_off = np.concatenate([[0], np.cumsum(sizes)])
    N = int(node_off[-1])
    parent = np.full(N, -1, np.int64)
    depth = np.zeros(N, np.int64)
    deg = np.zeros(N, np.int64)
    chain = float(spec["chain_prob"])
    for l in range(1, int(sizes.max())):
        mol = np.nonzero(sizes > l)[0]
        base = node_off[mol]
        p = (rng.random(len(mol)) * l).astype(np.int64)
        p[rng.random(len(mol)) < chain] = l - 1
        full = deg[base + p] >= max_deg
        p[full] = l - 1
        child, par = base + l, base + p
        parent[child] = par
        depth[child] = depth[par] + 1
        deg[par] += 1
        deg[child] += 1
    # ring closures to the 4th or 5th ancestor
    hop = np.where(rng.random(N) < 0.5, 4, 5)
    anc = np.arange(N)
    anc4 = np.arange(N)
    for step in range(5):
        anc = np.where(anc >= 0, parent[np.maximum(anc, 0)], -1)
        anc = np.where(depth >= step + 1, anc, -1)
        if step == 3:
            anc4 = anc.copy()
    target = np.where(hop == 4, anc4, anc)
    cand = np.nonzero((target >= 0) & (deg < max_deg))[0]
    extra = float(spec["mean_bonds"]) - float(sizes.mean() - 1.0)
    want = max(0.0, extra) * M
    rate = min(1.0, want / max(len(cand), 1))
    ring_a = ring_b = np.zeros(0, np.int64)
    for _ in range(3):  # top up what the valence check turned away
        pick = cand[rng.random(len(cand)) < rate]
        a, b = pick, target[pick]
        inc = np.bincount(np.concatenate([a, b]), minlength=N)
        ok = (deg[a] + inc[a] <= max_deg) & (deg[b] + inc[b] <= max_deg)
        a, b = a[ok], b[ok]
        # one closure per atom pair
        key = np.unique(np.minimum(a, b) * N + np.maximum(a, b))
        a, b = key // N, key % N
        ring_a, ring_b = np.concatenate([ring_a, a]), np.concatenate([ring_b, b])
        np.add.at(deg, a, 1)
        np.add.at(deg, b, 1)
        cand = np.setdiff1d(cand, np.concatenate([a, b]))
        cand = cand[deg[cand] < max_deg]
        cand = cand[deg[target[cand]] < max_deg]
        got = len(ring_a)
        if got >= want or not len(cand):
            break
        rate = min(1.0, (want - got) / len(cand))
    order = np.argsort(ring_a, kind="stable")
    ring_a, ring_b = ring_a[order], ring_b[order]

    # entries, grouped by molecule: self loops, tree (both ways), rings (both ways)
    mol_of = np.repeat(np.arange(M), sizes)
    child = np.nonzero(parent >= 0)[0]
    blocks = [
        (np.arange(N), np.arange(N)),
        (child, parent[child]),
        (parent[child], child),
        (ring_a, ring_b),
        (ring_b, ring_a),
    ]
    counts = [np.bincount(mol_of[r], minlength=M) for r, _ in blocks]
    per_mol = np.sum(counts, axis=0)
    entry_off = np.concatenate([[0], np.cumsum(per_mol)])
    E = int(entry_off[-1])
    g_rows = np.empty(E, np.int64)
    g_cols = np.empty(E, np.int64)
    within = np.zeros(M, np.int64)
    for (r, c), cnt in zip(blocks, counts):
        block_start = np.concatenate([[0], np.cumsum(cnt)])[:-1]
        # every block is sorted by molecule (node order, or ring_a order)
        key_mol = mol_of[r]
        idx = np.arange(len(r)) - np.repeat(block_start, cnt)
        pos = entry_off[key_mol] + within[key_mol] + idx
        g_rows[pos], g_cols[pos] = r, c
        within += cnt
    dfull = np.bincount(g_rows, minlength=N).astype(np.float64)  # bonds + 1
    vals = (1.0 / np.sqrt(dfull[g_rows] * dfull[g_cols])).astype(np.float32)
    entry_mol = np.repeat(np.arange(M), per_mol)
    local_rows = (g_rows - node_off[entry_mol]).astype(np.int32)
    local_cols = (g_cols - node_off[entry_mol]).astype(np.int32)
    probs = np.asarray(spec["atom_type_probs"], np.float64)
    atom_type = rng.choice(len(probs), size=N, p=probs / probs.sum()).astype(np.int32)
    return MoleculeSet(
        node_off=node_off,
        entry_off=entry_off,
        rows=local_rows,
        cols=local_cols,
        vals=vals,
        atom_type=atom_type,
        degree=(dfull - 1).astype(np.int32),
    )
