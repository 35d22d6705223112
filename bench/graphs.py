"""Graph generators of the benchmark, frozen with it.

The arithmetic is a copy of the repository's ``rgg2d`` and ``rmat``
generators (so the counts match the ones recorded for them), kept here so
that a later change to the program cannot change what the benchmark feeds
it.  Each generator returns the raw undirected draws ``(n, u, v)``; two
consumers read them independently:

* :func:`edge_list` coalesces them in numpy, the way the check in
  ``check.py`` needs them: unique pairs ``a < b`` with their multiplicity as
  weight, self loops dropped;
* ``program_graph`` in ``harness.py`` hands the same draws to the program's
  public ``from_coo``.
"""

from __future__ import annotations

import numpy as np


def _radius_graph(pts: np.ndarray, r: float) -> tuple[np.ndarray, np.ndarray]:
    """All pairs within distance r, via cell hashing (host, O(n * avg_deg))."""
    n, d = pts.shape
    cell = np.floor(pts / r).astype(np.int64)
    dims = cell.max(axis=0) + 1
    mult = np.cumprod(np.concatenate([[1], dims[:-1]]))
    key = cell @ mult
    order = np.argsort(key, kind="stable")
    key_s = key[order]
    offs = np.array(np.meshgrid(*([[-1, 0, 1]] * d), indexing="ij")).reshape(d, -1).T
    uniq = np.unique(key_s)
    starts = np.searchsorted(key_s, uniq)
    cell_of = {int(k): i for i, k in enumerate(uniq)}
    bounds = np.append(starts, n)
    us, vs = [], []
    for off in offs:
        nk = key + off @ mult
        for i in range(n):
            j = cell_of.get(int(nk[i]))
            if j is None:
                continue
            cand = order[bounds[j]:bounds[j + 1]]
            cand = cand[cand > i]
            if len(cand) == 0:
                continue
            dist2 = ((pts[cand] - pts[i]) ** 2).sum(axis=1)
            hit = cand[dist2 <= r * r]
            if len(hit):
                us.append(np.full(len(hit), i, np.int64))
                vs.append(hit.astype(np.int64))
    if not us:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    return np.concatenate(us), np.concatenate(vs)


def rgg2d(n: int, avg_deg: float, seed: int):
    """Random geometric graph in the unit square (rgg2D): n points drawn
    uniformly, an edge between every pair closer than
    r = sqrt(avg_deg / (pi * n))."""
    rng = np.random.default_rng(seed)
    pts = rng.random((n, 2))
    r = float(np.sqrt(avg_deg / (np.pi * n)))
    u, v = _radius_graph(pts, r)
    return n, u, v


def rmat(scale: int, edge_factor: int, a: float, b: float, c: float,
         seed: int):
    """R-MAT / Kronecker generator (Graph500): 2**scale vertices and
    edge_factor * 2**scale draws, one quadrant choice per bit."""
    n = 1 << scale
    m = n * edge_factor
    rng = np.random.default_rng(seed)
    u = np.zeros(m, dtype=np.int64)
    v = np.zeros(m, dtype=np.int64)
    for lvl in range(scale):
        r = rng.random(m)
        right = r > a + b
        down = (r > a) & (r <= a + b) | (r > a + b + c)
        u |= down.astype(np.int64) << lvl
        v |= right.astype(np.int64) << lvl
    keep = u != v
    return n, u[keep], v[keep]


GENERATORS = {"rgg2d": rgg2d, "rmat": rmat}


def generate(spec: dict, seed: int):
    """``(n, u, v)`` of the configuration's ``graph`` block for ``seed``."""
    params = {k: v for k, v in spec.items() if k != "generator"}
    return GENERATORS[spec["generator"]](seed=seed, **params)


def edge_list(u: np.ndarray, v: np.ndarray):
    """Unique undirected pairs ``(a, b)``, ``a < b``, and their multiplicity
    among the draws (self loops dropped)."""
    keep = u != v
    a = np.minimum(u[keep], v[keep])
    b = np.maximum(u[keep], v[keep])
    pairs, w = np.unique(np.stack([a, b], axis=1), axis=0, return_counts=True)
    return pairs[:, 0], pairs[:, 1], w.astype(np.int64)
