"""Pallas gain kernel: shape/dtype sweeps vs the pure-jnp oracle (ref.py)
and vs the production best_moves path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import best_moves
from repro.core.graph import PAD
from repro.graphs import grid2d, rmat, chung_lu_powerlaw
from repro.kernels.gain import gain_scoreboard, pad_for_kernel
from repro.kernels.gain.kernel import gain_scoreboard_pallas
from repro.kernels.gain.ref import gain_scoreboard_ref


def _compare(g, k, seed=0, capacity=None):
    labels = jax.random.randint(jax.random.PRNGKey(seed), (g.n,), 0, k, dtype=jnp.int32)
    maxdeg = max(int(np.asarray(g.degrees).max()), 1)
    nbr, nbr_w = pad_for_kernel(g, maxdeg)
    cap = jnp.full((k,), jnp.inf) if capacity is None else capacity
    got = gain_scoreboard(nbr, nbr_w, labels, g.nw, cap, k)
    want = best_moves(g, labels, k, capacity=capacity)
    for name, x, y in zip(("own", "gain", "tgt"), got, want):
        x = np.nan_to_num(np.asarray(x, np.float64), neginf=-1e30)
        y = np.nan_to_num(np.asarray(y, np.float64), neginf=-1e30)
        np.testing.assert_allclose(x, y, rtol=1e-5, atol=1e-5, err_msg=name)


@pytest.mark.parametrize("k", [2, 8, 128, 130])  # 130 → lane padding path
def test_kernel_vs_best_moves_grid(k):
    _compare(grid2d(16, 16), k)


@pytest.mark.parametrize("graph_fn,kwargs", [
    (rmat, dict(scale=8, edge_factor=4, seed=1)),
    (chung_lu_powerlaw, dict(n=512, avg_deg=8, seed=2)),
])
def test_kernel_vs_best_moves_irregular(graph_fn, kwargs):
    _compare(graph_fn(**kwargs), 8)


def test_kernel_capacity_mode():
    g = grid2d(16, 16)
    cap = jnp.asarray(np.random.default_rng(0).uniform(0, 2, 8).astype(np.float32))
    _compare(g, 8, capacity=cap)


@pytest.mark.parametrize("tile_n,deg_chunk", [(128, 8), (256, 16), (512, 32)])
def test_kernel_block_shapes(tile_n, deg_chunk):
    """BlockSpec tiling sweep: results independent of tile configuration."""
    g = rmat(scale=8, edge_factor=4, seed=4)
    k = 8
    labels = jax.random.randint(jax.random.PRNGKey(0), (g.n,), 0, k, dtype=jnp.int32)
    maxdeg = int(np.asarray(g.degrees).max())
    nbr, nbr_w = pad_for_kernel(g, maxdeg, tile_n=tile_n, deg_chunk=deg_chunk)
    cap = jnp.full((k,), jnp.inf)
    got = gain_scoreboard(nbr, nbr_w, labels, g.nw, cap, k,
                          tile_n=tile_n, deg_chunk=deg_chunk)
    want = best_moves(g, labels, k)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]), rtol=1e-5)


@given(
    n_tiles=st.integers(1, 3),
    deg=st.integers(1, 3),
    k=st.sampled_from([4, 8]),
    seed=st.integers(0, 2**31),
)
@settings(max_examples=20, deadline=None)
def test_kernel_property_random_padded(n_tiles, deg, k, seed):
    """Direct kernel-vs-oracle on arbitrary padded adjacency (incl. PAD rows)."""
    rng = np.random.default_rng(seed)
    n = 128 * n_tiles
    d = 16 * deg
    nbr_lab = rng.integers(0, k, (n, d)).astype(np.int32)
    pad_mask = rng.random((n, d)) < 0.3
    nbr_lab[pad_mask] = int(PAD)
    nbr_w = rng.uniform(0, 3, (n, d)).astype(np.float32)
    nbr_w[pad_mask] = 0.0
    labels = rng.integers(0, k, n).astype(np.int32)
    nw = rng.uniform(0.5, 2, n).astype(np.float32)
    kp = 128
    cap = np.full(kp, -np.inf, np.float32)
    cap[:k] = rng.uniform(0, 3, k)

    got = gain_scoreboard_pallas(  # the kernel takes (D, N), returns (N,)
        jnp.asarray(nbr_lab.T), jnp.asarray(nbr_w.T), jnp.asarray(labels),
        jnp.asarray(nw), jnp.asarray(cap), tile_n=128, deg_chunk=16,
        interpret=True,
    )
    want = gain_scoreboard_ref(
        jnp.asarray(nbr_lab), jnp.asarray(nbr_w), jnp.asarray(labels),
        jnp.asarray(nw), jnp.asarray(cap),
    )
    for name, x, y in zip(("own", "gain", "tgt"), got, want):
        x = np.nan_to_num(np.asarray(x, np.float64), neginf=-1e30)
        x = np.where(x < -1e29, -1e30, x)
        y = np.nan_to_num(np.asarray(y, np.float64)[:, 0], neginf=-1e30)
        np.testing.assert_allclose(x, y, rtol=1e-4, atol=1e-4, err_msg=name)


def test_kernel_dtype_bf16_weights():
    """bf16 edge weights upcast consistently."""
    g = grid2d(16, 16)
    k = 8
    labels = jax.random.randint(jax.random.PRNGKey(0), (g.n,), 0, k, dtype=jnp.int32)
    nbr, nbr_w = pad_for_kernel(g, 4)
    cap = jnp.full((k,), jnp.inf)
    a = gain_scoreboard(nbr, nbr_w.astype(jnp.bfloat16).astype(jnp.float32),
                        labels, g.nw, cap, k)
    b = gain_scoreboard(nbr, nbr_w, labels, g.nw, cap, k)
    np.testing.assert_allclose(np.asarray(a[0]), np.asarray(b[0]), rtol=1e-2)
