"""jit'd public wrapper around the gain scoreboard kernel.

On TPU this lowers to the compiled Mosaic kernel; on CPU the kernel body
executes in interpret mode — same code path, Python-evaluated — so the
BlockSpec tiling is validated for correctness off-chip
(``tests/test_tpu_compile.py`` compiles it for a described v5e).

This is the *standalone* wrapper (whole-graph padded adjacency, labels
gathered here) used by the kernel tests and benchmarks; the production hot
path feeds the kernel through ``repro.refine.gain.PallasGain``, which
builds a per-level edge-slot adjacency once and reuses it every round
under any comm backend.
"""

from __future__ import annotations

from functools import partial
from typing import TYPE_CHECKING

import jax
import jax.numpy as jnp

from repro.kernels import default_interpret
from repro.kernels.gain.kernel import LANE, gain_scoreboard_pallas, round_up

if TYPE_CHECKING:  # runtime import is deferred: breaks the core↔refine cycle
    from repro.core.graph import Graph

_round_up = round_up  # single definition lives with the kernel


def pad_for_kernel(g: Graph, max_deg: int, tile_n: int = 256, deg_chunk: int = 16):
    """Padded-adjacency arrays sized for the kernel: N → multiple of tile_n,
    D → multiple of deg_chunk.  Labels of neighbours are substituted by the
    caller per round; this returns neighbour *ids* + weights."""
    from repro.core.graph import PAD, to_padded_fast

    d = _round_up(max(max_deg, 1), deg_chunk)
    nbr, nbr_w = to_padded_fast(g, d)
    n_pad = _round_up(g.n, tile_n)
    if n_pad != g.n:
        nbr = jnp.pad(nbr, ((0, n_pad - g.n), (0, 0)), constant_values=int(PAD))
        nbr_w = jnp.pad(nbr_w, ((0, n_pad - g.n), (0, 0)))
    return nbr, nbr_w


@partial(jax.jit, static_argnames=("k", "tile_n", "deg_chunk", "interpret"))
def gain_scoreboard(
    nbr: jax.Array,        # (N, D) neighbour ids (PAD-padded)
    nbr_w: jax.Array,      # (N, D)
    labels: jax.Array,     # (n,) block labels of *all* vertices
    nw: jax.Array,         # (n,) vertex weights
    capacity: jax.Array,   # (k,) remaining block capacity (+inf = Jet mode)
    k: int,
    tile_n: int = 256,
    deg_chunk: int = 16,
    interpret: bool | None = None,
):
    """Returns (own, gain, target), each (n,) — matching partition.best_moves.

    ``nbr`` holds neighbour *ids*; the label gather happens here so one padded
    adjacency serves every round.
    """
    from repro.core.graph import PAD

    interpret = default_interpret(interpret)
    n_pad = nbr.shape[0]
    n = labels.shape[0]

    # gather labels of neighbours; PAD slots stay PAD (match no block)
    safe = jnp.where(nbr == PAD, 0, nbr)
    nbr_lab = jnp.where(nbr == PAD, PAD, labels[safe])

    k_pad = _round_up(k, LANE)
    cap = jnp.full((k_pad,), -jnp.inf, jnp.float32).at[:k].set(capacity)

    lab_p = jnp.pad(labels, (0, n_pad - n))
    nw_p = jnp.pad(nw, (0, n_pad - n))

    own, gain, tgt = gain_scoreboard_pallas(
        nbr_lab.T, nbr_w.T, lab_p, nw_p, cap,
        tile_n=tile_n, deg_chunk=deg_chunk, interpret=interpret,
    )
    return own[:n], gain[:n], tgt[:n]
