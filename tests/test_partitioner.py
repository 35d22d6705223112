"""Core partitioner behaviour: metrics, Jet, rebalance, multilevel quality."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    best_moves,
    block_weights,
    conn_dense,
    edge_cut,
    exact_edge_cut,
    imbalance,
    jet_round,
    l_max,
    partition,
    rebalance,
    total_overload,
)
from repro.core.refine import temperature_schedule
from repro.graphs import grid2d, rmat, ring


@pytest.fixture(scope="module")
def grid():
    return grid2d(24, 24)


@pytest.fixture(scope="module")
def power():
    return rmat(scale=9, edge_factor=6, seed=3)


def rand_labels(g, k, seed=0):
    return jax.random.randint(jax.random.PRNGKey(seed), (g.n,), 0, k, dtype=jnp.int32)


# --------------------------------------------------------------------------
# metrics
# --------------------------------------------------------------------------

def test_edge_cut_bruteforce(grid):
    labels = rand_labels(grid, 3)
    src = np.asarray(grid.src)
    col = np.asarray(grid.safe_col())
    ew = np.asarray(grid.ew)
    live = np.asarray(grid.edge_mask)
    lab = np.asarray(labels)
    brute = ew[live & (lab[src] != lab[col])].sum() / 2
    assert float(edge_cut(grid, labels)) == pytest.approx(float(brute))


def test_ring_cut_two_blocks():
    g = ring(16)
    labels = jnp.asarray(([0] * 8) + ([1] * 8), dtype=jnp.int32)
    assert float(edge_cut(g, labels)) == 2.0  # two boundary edges


def test_exact_edge_cut_beyond_fp32_range():
    """The reported cut stays exact where the directed total passes 2^24
    (here ~8e8, odd integer weights < 2^14): one fp32 accumulator rounds
    it, the chunked float64 total does not."""
    from repro.core.graph import from_coo
    from repro.distributed.dgraph import (
        labels_to_sharded,
        shard_graph,
        sharded_edge_cut,
    )

    rng = np.random.default_rng(0)
    n = 1 << 16
    u = np.arange(n)
    w = rng.integers(1, 8192, n).astype(np.float32) * 2 - 1
    g = from_coo(n, u, (u + 1) % n, w)
    lab = rng.integers(0, 4, n).astype(np.int32)
    src, col = np.asarray(g.src), np.asarray(g.col)
    want = np.asarray(g.ew, np.float64)[lab[src] != lab[col]].sum() / 2
    labels = jnp.asarray(lab)
    assert exact_edge_cut(g, labels) == want
    sg = shard_graph(g, 1)
    assert sharded_edge_cut(sg, labels_to_sharded(sg, labels)) == want


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_top_candidates_equals_lexsort(seed):
    """The greedy rebalancer's sort-free top-k selects exactly what the
    lexsort it replaced selects, ties and -inf fillers included."""
    from repro.refine.engine import top_candidates

    rng = np.random.default_rng(seed)
    n = int(rng.integers(100, 3000))
    score = rng.integers(-3, 3, n).astype(np.float32)
    score[rng.random(n) < 0.4] = -np.inf
    tid = rng.integers(0, 50, n).astype(np.int32)
    tid[rng.random(n) < 0.2] = np.iinfo(np.int32).max
    got = top_candidates(jnp.asarray(score), jnp.asarray(tid), 128)
    want = jnp.lexsort((jnp.asarray(tid), -jnp.asarray(score)))[:128]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("P_", [1, 4, 8, 9, 16])
def test_bucket_rank_equals_stable_sort(P_):
    """The contraction's bucket rank — a counting sort up to
    COUNTING_SORT_MAX_P PEs, one stable argsort above — is each slot's rank
    within its destination bucket in slot order."""
    from repro.distributed.dcoarsen import bucket_rank

    rng = np.random.default_rng(P_)
    dest = rng.integers(0, P_ + 1, 2000).astype(np.int32)
    got = np.asarray(bucket_rank(jnp.asarray(dest), P_))
    order = np.argsort(dest, kind="stable")
    want = np.empty_like(dest)
    want[order] = np.arange(dest.size) - np.searchsorted(dest[order],
                                                         dest[order])
    live = dest < P_
    np.testing.assert_array_equal(got[live], want[live])


def test_cluster_shrinks_uniform_grid():
    """On a uniform mesh the LP clustering must coarsen: before the
    joiner/receiver coin flip every vertex chased its smallest-id
    neighbour and a 2^16-vertex grid shrank by < 5 % in one level."""
    from repro.core.coarsen import cluster

    g = grid2d(256, 256)
    cl = np.asarray(cluster(g, 128.0, jax.random.PRNGKey(0)))
    assert len(np.unique(cl)) < g.n // 3


def test_conn_dense_rowsum_equals_degreesum(grid):
    labels = rand_labels(grid, 4)
    conn = conn_dense(grid, labels, 4)
    # row sums = weighted degree
    deg_w = np.zeros(grid.n, np.float32)
    np.add.at(deg_w, np.asarray(grid.src), np.asarray(grid.ew))
    np.testing.assert_allclose(np.asarray(conn.sum(1)), deg_w, rtol=1e-5)


def test_best_moves_matches_conn(grid):
    k = 5
    labels = rand_labels(grid, k, seed=2)
    own, gain, tgt = best_moves(grid, labels, k)
    conn = np.asarray(conn_dense(grid, labels, k))
    lab = np.asarray(labels)
    np.testing.assert_allclose(np.asarray(own), conn[np.arange(grid.n), lab], rtol=1e-6)
    masked = conn.copy()
    masked[np.arange(grid.n), lab] = -np.inf
    np.testing.assert_allclose(
        np.asarray(gain), masked.max(1) - conn[np.arange(grid.n), lab], rtol=1e-6
    )


# --------------------------------------------------------------------------
# Jet round semantics
# --------------------------------------------------------------------------

def test_jet_round_does_not_increase_cut(grid, power):
    for g in (grid, power):
        for seed in range(3):
            labels = rand_labels(g, 4, seed)
            cut0 = float(edge_cut(g, labels))
            for tau in (0.0, 0.5, 1.0):
                res = jet_round(g, labels, jnp.zeros(g.n, bool), 4, tau)
                assert float(edge_cut(g, res.labels)) <= cut0 + 1e-4, (seed, tau)


def test_jet_round_locks_and_moves(grid):
    labels = rand_labels(grid, 4, seed=1)
    res = jet_round(grid, labels, jnp.zeros(grid.n, bool), 4, 0.5)
    assert int(res.n_moved) > 0
    # locked == moved mask
    assert int(res.locked.sum()) == int(res.n_moved)
    # a fully locked graph moves nothing
    res2 = jet_round(grid, labels, jnp.ones(grid.n, bool), 4, 0.5)
    assert int(res2.n_moved) == 0


def test_temperature_schedule_endpoints():
    taus = temperature_schedule(4)
    assert taus[0] == pytest.approx(0.75)
    assert taus[-1] == pytest.approx(0.25)
    assert temperature_schedule(1) == [0.25]


# --------------------------------------------------------------------------
# rebalance
# --------------------------------------------------------------------------

def test_rebalance_restores_balance(grid):
    k = 4
    # heavily skewed labels: 80% of vertices in block 0
    lab = np.zeros(grid.n, np.int32)
    rng = np.random.default_rng(0)
    idx = rng.permutation(grid.n)
    lab[idx[: grid.n // 5]] = rng.integers(1, k, grid.n // 5)
    labels = jnp.asarray(lab)
    lmax = l_max(grid, k, 0.03)
    assert float(total_overload(grid, labels, k, lmax)) > 0
    res = rebalance(grid, labels, k, lmax, jax.random.PRNGKey(0))
    assert float(res.overload) == 0.0
    assert float(imbalance(grid, res.labels, k)) <= 0.03 + 1e-6


def test_rebalance_noop_when_balanced(grid):
    k = 4
    labels = jnp.asarray(np.arange(grid.n) % k, dtype=jnp.int32)
    lmax = l_max(grid, k, 0.03)
    res = rebalance(grid, labels, k, lmax, jax.random.PRNGKey(0))
    assert int(res.epochs) == 0
    np.testing.assert_array_equal(np.asarray(res.labels), np.asarray(labels))


# --------------------------------------------------------------------------
# multilevel end-to-end quality
# --------------------------------------------------------------------------

@pytest.mark.parametrize("k", [2, 4])
def test_partition_balanced_and_reasonable(grid, k):
    res = partition(grid, k=k, eps=0.03, seed=0, refiner="d4xjet", max_inner=16)
    assert res.imbalance <= 0.03 + 1e-6
    # a 24x24 grid cut into k balanced chunks: boundary ≲ 4·24·k
    assert res.cut <= 4 * 24 * k


def test_jet_beats_lp(grid):
    jet = partition(grid, k=4, eps=0.03, seed=0, refiner="d4xjet", max_inner=16)
    lp = partition(grid, k=4, eps=0.03, seed=0, refiner="dlp")
    assert jet.imbalance <= 0.03 + 1e-6
    assert lp.imbalance <= 0.03 + 1e-6
    assert jet.cut <= lp.cut  # paper Fig. 1a at small scale


def test_partition_powerlaw(power):
    res = partition(power, k=4, eps=0.03, seed=0, refiner="d4xjet", max_inner=12)
    assert res.imbalance <= 0.03 + 1e-6
    total = float(power.total_edge_weight) / 2
    assert res.cut < total  # strictly better than random-ish everything-cut
