"""Synthetic graph generators standing in for the paper's benchmark set.

The paper evaluates on (a) real-world graphs from Table 2 — not
redistributable here — and (b) randomly generated rgg (random geometric) and
rhg (random hyperbolic, power-law exponent 3.0) graphs for the scaling study
(Fig. 2a).  We generate the same *classes*:

* low max-degree, mesh-like:   ``grid2d`` / ``grid3d`` / ``rgg2d`` / ``rgg3d``
  (stand-ins for nlpkkt240, europe.osm, del*/rgg* instances)
* high max-degree, power-law:  ``chung_lu_powerlaw`` (exponent 3.0, the rhg
  stand-in) and ``rmat`` (twitter/uk-2007-like skew)
* small-world:                 ``watts_strogatz``

All generators are host-side numpy (graph construction is data ingestion) and
deterministic given a seed.
"""

from __future__ import annotations

import numpy as np

from repro.core.graph import Graph, from_coo


def ring(n: int, w: float = 1.0) -> Graph:
    u = np.arange(n, dtype=np.int64)
    v = (u + 1) % n
    return from_coo(n, u, v, np.full(n, w, np.float32))


def grid2d(nx: int, ny: int, torus: bool = False, seed: int = 0) -> Graph:
    """nx*ny lattice; the low-degree mesh-like class (Δ ≤ 4)."""
    n = nx * ny
    idx = np.arange(n, dtype=np.int64)
    x, y = idx % nx, idx // nx
    es, ed = [], []
    right = x + 1 < nx
    es.append(idx[right]); ed.append(idx[right] + 1)
    up = y + 1 < ny
    es.append(idx[up]); ed.append(idx[up] + nx)
    if torus:
        es.append(idx[x == nx - 1]); ed.append(idx[x == nx - 1] - (nx - 1))
        es.append(idx[y == ny - 1]); ed.append(idx[y == ny - 1] - (ny - 1) * nx)
    return from_coo(n, np.concatenate(es), np.concatenate(ed))


def grid3d(nx: int, ny: int, nz: int) -> Graph:
    n = nx * ny * nz
    idx = np.arange(n, dtype=np.int64)
    x = idx % nx
    y = (idx // nx) % ny
    z = idx // (nx * ny)
    es, ed = [], []
    for cond, off in (((x + 1 < nx), 1), ((y + 1 < ny), nx), ((z + 1 < nz), nx * ny)):
        es.append(idx[cond]); ed.append(idx[cond] + off)
    return from_coo(n, np.concatenate(es), np.concatenate(ed))


def _radius_graph(pts: np.ndarray, r: float) -> tuple[np.ndarray, np.ndarray]:
    """All pairs within distance r, via cell hashing (host, O(n · avg_deg))."""
    n, d = pts.shape
    cell = np.floor(pts / r).astype(np.int64)
    dims = cell.max(axis=0) + 1
    mult = np.cumprod(np.concatenate([[1], dims[:-1]]))
    key = cell @ mult
    order = np.argsort(key, kind="stable")
    key_s = key[order]
    # neighbouring cell offsets
    offs = np.array(np.meshgrid(*([[-1, 0, 1]] * d), indexing="ij")).reshape(d, -1).T
    us, vs = [], []
    starts = np.searchsorted(key_s, np.unique(key_s))
    uniq = np.unique(key_s)
    cell_of = {int(k): i for i, k in enumerate(uniq)}
    bounds = np.append(starts, n)
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


def rgg2d(n: int, avg_deg: float = 8.0, seed: int = 0) -> Graph:
    """Random geometric graph in the unit square (paper's rgg2D class)."""
    rng = np.random.default_rng(seed)
    pts = rng.random((n, 2))
    r = float(np.sqrt(avg_deg / (np.pi * n)))
    u, v = _radius_graph(pts, r)
    return from_coo(n, u, v)


def rgg3d(n: int, avg_deg: float = 10.0, seed: int = 0) -> Graph:
    rng = np.random.default_rng(seed)
    pts = rng.random((n, 3))
    r = float((avg_deg / (4.0 / 3.0 * np.pi * n)) ** (1.0 / 3.0))
    u, v = _radius_graph(pts, r)
    return from_coo(n, u, v)


def chung_lu_powerlaw(
    n: int, avg_deg: float = 16.0, exponent: float = 3.0, seed: int = 0
) -> Graph:
    """Chung–Lu graph with power-law expected degrees (exponent 3.0) — the
    rhg stand-in used for the high-degree / scale-free class."""
    rng = np.random.default_rng(seed)
    # expected degrees w_i ∝ (i+1)^(-1/(exponent-1)), scaled to avg_deg
    ranks = np.arange(1, n + 1, dtype=np.float64)
    w = ranks ** (-1.0 / (exponent - 1.0))
    w *= avg_deg * n / w.sum()
    total = w.sum()
    m_target = int(avg_deg * n / 2)
    p = w / total
    u = rng.choice(n, size=2 * m_target, p=p).astype(np.int64)
    v = rng.choice(n, size=2 * m_target, p=p).astype(np.int64)
    keep = u != v
    return from_coo(n, u[keep][:m_target * 2], v[keep][:m_target * 2])


def rmat(
    scale: int,
    edge_factor: int = 8,
    a: float = 0.57, b: float = 0.19, c: float = 0.19,
    seed: int = 0,
) -> Graph:
    """R-MAT / Kronecker generator (Graph500 parameters) — web/social skew."""
    n = 1 << scale
    m = n * edge_factor
    rng = np.random.default_rng(seed)
    u = np.zeros(m, dtype=np.int64)
    v = np.zeros(m, dtype=np.int64)
    for lvl in range(scale):
        r = rng.random(m)
        right = r > a + b        # falls in c or d quadrant → v bit set
        down = (r > a) & (r <= a + b) | (r > a + b + c)  # b or d → u bit set
        u |= down.astype(np.int64) << lvl
        v |= right.astype(np.int64) << lvl
    keep = u != v
    return from_coo(n, u[keep], v[keep])


def watts_strogatz(n: int, k: int = 6, beta: float = 0.1, seed: int = 0) -> Graph:
    rng = np.random.default_rng(seed)
    base = np.arange(n, dtype=np.int64)
    us, vs = [], []
    for off in range(1, k // 2 + 1):
        u = base
        v = (base + off) % n
        rewire = rng.random(n) < beta
        v = np.where(rewire, rng.integers(0, n, n), v)
        keep = u != v
        us.append(u[keep]); vs.append(v[keep])
    return from_coo(n, np.concatenate(us), np.concatenate(vs))


# --------------------------------------------------------------------------
# Benchmark registry — mirrors the paper's Table 2 classes.
# name -> (factory, kwargs, class) ; most sizes run the full multilevel
# pipeline in seconds on one CPU device, the *_8m/*_21 ones are chip-scale.
# --------------------------------------------------------------------------
BENCHMARK_SET = {
    # low-degree / mesh-like (paper: nlpkkt240, europe.osm, rgg*, del*)
    "grid2d_64k": (grid2d, dict(nx=256, ny=256), "low"),
    "grid3d_32k": (grid3d, dict(nx=32, ny=32, nz=32), "low"),
    "torus_16k": (grid2d, dict(nx=128, ny=128, torus=True), "low"),
    "rgg2d_16k": (rgg2d, dict(n=16384, avg_deg=8.0, seed=1), "low"),
    "rgg3d_8k": (rgg3d, dict(n=8192, avg_deg=10.0, seed=2), "low"),
    # high-degree / power-law (paper: twitter-2010, uk-2007, com-orkut)
    "rhg_16k": (chung_lu_powerlaw, dict(n=16384, avg_deg=16.0, seed=3), "high"),
    "rhg_32k": (chung_lu_powerlaw, dict(n=32768, avg_deg=12.0, seed=4), "high"),
    "rmat_14": (rmat, dict(scale=14, edge_factor=8, seed=5), "high"),
    "rmat_15": (rmat, dict(scale=15, edge_factor=6, seed=6), "high"),
    "ws_16k": (watts_strogatz, dict(n=16384, k=8, beta=0.05, seed=7), "low"),
    # chip-scale instances (one TPU v5e, a few GiB of its 16 GB HBM):
    # grid2d_8m stays below the 2^24 unit-weight bound of fp32 exactness
    # (DESIGN.md §1); rmat_21 is Graph500's Kronecker generator at scale 21
    # (a=.57, b=.19, c=.19, edge factor 16 — 33.5M edges before dedup)
    "grid2d_8m": (grid2d, dict(nx=2048, ny=4096), "low"),
    # the mesh that one chip refines within the smoke run's time limit
    # (chip_smoke.py; grid2d_8m's refinement is gather-bound there, PERF.md)
    "grid2d_256k": (grid2d, dict(nx=512, ny=512), "low"),
    "rmat_21": (rmat, dict(scale=21, edge_factor=16, seed=5), "high"),
    # tiny smoke instances — seconds-scale CLI subprocess tests
    # (tests/test_kill_resume.py) and quick local runs, not benchmark cells
    "grid2d_1k": (grid2d, dict(nx=32, ny=32), "low"),
    "rmat_9": (rmat, dict(scale=9, edge_factor=4, seed=5), "high"),
}


def generate(name: str) -> Graph:
    fac, kw, _cls = BENCHMARK_SET[name]
    return fac(**kw)
