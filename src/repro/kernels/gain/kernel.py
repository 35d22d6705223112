"""Pallas TPU kernel: per-vertex block-connectivity scoreboard.

This is the partitioner's compute hot-spot — every Jet round, every LP round
and every rebalance epoch evaluates, for each vertex v,

    conn(v, V_j) = Σ_{(v,u) ∈ E, u ∈ V_j} ω(v,u)          for all j,
    own(v)       = conn(v, V_own),
    gain(v)      = max_{j eligible} conn(v, V_j) − own(v),
    target(v)    = argmax_{j eligible} conn(v, V_j),

with eligibility j ≠ own(v) ∧ capacity[j] ≥ c(v) (capacity = +inf reproduces
unconstrained Jet move generation; capacity = L_max − c(V_u) reproduces the
rebalancer's feasible-target rule).

TPU adaptation (vs the paper's CPU hash tables / Jet's GPU gather loops):
instead of per-vertex hash tables we keep a dense (K, TILE_N) *scoreboard*
— blocks on sublanes, vertices on lanes — and fold in one neighbour row at
a time with a 2-D compare-accumulate: ``score += where(lab_r == blk, w_r,
0)``, where ``lab_r``/``w_r`` are the (1, TILE_N) labels/weights of every
tile vertex's r-th neighbour, broadcast over the K sublanes.  The padded
adjacency is therefore stored *neighbour-major*, (D, N): the degree axis
walks sublanes, so the fori_loop over DEG_CHUNK-row chunks is a
sublane-aligned dynamic slice (Mosaic refuses dynamic lane slices that it
cannot prove 128-aligned), and the rows inside a chunk are static slices.
Padding slots carry label PAD = int32::max, which matches no block, and
weight 0, so they are inert.

Compiled-path tiling: TILE_N is a multiple of 128 (lanes), DEG_CHUNK a
multiple of 8 (sublanes), K padded to a multiple of 128.  VMEM per program
instance (fp32/int32, double-buffered inputs):

    neighbour tiles   2 inputs · 2 buffers · D · TILE_N · 4
    scoreboard + epilogue temporaries   ≈ 6 · K · TILE_N · 4

At TILE_N = 256: D = 2048 costs 8 MiB of tiles, K = 1024 about 6 MiB of
scoreboard — inside the 16 MiB scoped-VMEM default at the envelope corner
(DESIGN.md §5; ``tests/test_tpu_compile.py`` compiles the corners for v5e).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG = -3.0e38  # sentinel "-inf" that survives fp32 arithmetic
LANE = 128     # TPU lane width: K and TILE_N are multiples of this
SUBLANE = 8    # TPU sublane count: DEG_CHUNK is a multiple of this


def round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def _gain_kernel(
    nbr_ref,       # (D, TILE_N) int32 — neighbour labels, PAD-padded
    nbrw_ref,      # (D, TILE_N) f32
    labels_ref,    # (1, TILE_N) int32 — own block
    nw_ref,        # (1, TILE_N) f32   — vertex weight
    cap_ref,       # (K, 1) f32        — per-block remaining capacity
    own_ref,       # (1, TILE_N) f32   out
    gain_ref,      # (1, TILE_N) f32   out
    tgt_ref,       # (1, TILE_N) int32 out
    *,
    deg_chunk: int,
):
    d, tile_n = nbr_ref.shape
    k = cap_ref.shape[0]
    blk = jax.lax.broadcasted_iota(jnp.int32, (k, tile_n), 0)   # (K, T)

    def body(c, score):
        rows = pl.ds(pl.multiple_of(c * deg_chunk, deg_chunk), deg_chunk)
        lab = nbr_ref[rows, :]                                   # (DC, T)
        w = nbrw_ref[rows, :]                                    # (DC, T)
        for r in range(deg_chunk):
            score = score + jnp.where(lab[r:r + 1, :] == blk,
                                      w[r:r + 1, :], 0.0)
        return score

    score = jax.lax.fori_loop(
        0, d // deg_chunk, body, jnp.zeros((k, tile_n), jnp.float32)
    )

    lab_own = labels_ref[:1, :]                                  # (1, T)
    own = jnp.sum(jnp.where(blk == lab_own, score, 0.0), axis=0,
                  keepdims=True)
    eligible = (blk != lab_own) & (cap_ref[:, :1] >= nw_ref[:1, :])
    masked = jnp.where(eligible, score, NEG)
    best = jnp.max(masked, axis=0, keepdims=True)
    # first maximal block index — jnp.argmax's tie-break, spelled as a
    # min-reduction so it lowers on the sublane axis
    tgt = jnp.min(jnp.where(masked == best, blk, k), axis=0, keepdims=True)

    own_ref[:, :] = own
    gain_ref[:, :] = jnp.where(best <= NEG / 2, -jnp.inf, best - own)
    tgt_ref[:, :] = jnp.where(best <= NEG / 2, lab_own, tgt)


@functools.partial(
    jax.jit, static_argnames=("tile_n", "deg_chunk", "interpret")
)
def gain_scoreboard_pallas(
    nbr_labels: jax.Array,   # (D, N) int32, PAD where unused (N % tile_n == 0)
    nbr_w: jax.Array,        # (D, N) f32
    labels: jax.Array,       # (N,) int32
    nw: jax.Array,           # (N,) f32
    capacity: jax.Array,     # (K,) f32, K % 128 == 0
    *,
    tile_n: int = 256,
    deg_chunk: int = 16,
    interpret: bool = False,
):
    """Returns (own, gain, target), each (N,), from the neighbour-major
    padded adjacency.  The compiled (non-interpret) path needs the TPU
    tiling: ``tile_n % 128 == 0`` and ``deg_chunk % 8 == 0``."""
    d, n = nbr_labels.shape
    k = capacity.shape[0]
    if n % tile_n or d % deg_chunk:
        raise ValueError(f"(D, N)=({d}, {n}) must be multiples of "
                         f"(deg_chunk, tile_n)=({deg_chunk}, {tile_n})")
    if not interpret and (tile_n % LANE or deg_chunk % SUBLANE):
        raise ValueError(f"compiled gain kernel needs tile_n % {LANE} == 0 "
                         f"and deg_chunk % {SUBLANE} == 0, got "
                         f"tile_n={tile_n}, deg_chunk={deg_chunk}")
    grid = (n // tile_n,)

    out_shapes = (
        jax.ShapeDtypeStruct((1, n), jnp.float32),
        jax.ShapeDtypeStruct((1, n), jnp.float32),
        jax.ShapeDtypeStruct((1, n), jnp.int32),
    )
    col = lambda i: (0, i)
    whole = lambda i: (0, 0)
    own, gain, tgt = pl.pallas_call(
        functools.partial(_gain_kernel, deg_chunk=deg_chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((d, tile_n), col),
            pl.BlockSpec((d, tile_n), col),
            pl.BlockSpec((1, tile_n), col),
            pl.BlockSpec((1, tile_n), col),
            pl.BlockSpec((k, 1), whole),
        ],
        out_specs=(
            pl.BlockSpec((1, tile_n), col),
            pl.BlockSpec((1, tile_n), col),
            pl.BlockSpec((1, tile_n), col),
        ),
        out_shape=out_shapes,
        interpret=interpret,
    )(
        nbr_labels,
        nbr_w,
        labels[None, :],
        nw[None, :],
        capacity[:, None],
    )
    return own[0], gain[0], tgt[0]
