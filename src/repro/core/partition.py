"""Partition state and metrics.

Terminology follows the paper: partition Π = {V_1..V_k}; balance constraint
c(V_i) ≤ L_max := (1+ε)·⌈c(V)/k⌉; objective = total weight of cut edges.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.core.graph import Graph


def l_max(g: Graph, k: int, eps: float) -> jax.Array:
    """Balance bound L_max = (1+ε)·⌈c(V)/k⌉ (paper §1)."""
    return (1.0 + eps) * jnp.ceil(g.total_node_weight / k)


@partial(jax.jit, static_argnames=("k",))
def block_weights(g: Graph, labels: jax.Array, k: int) -> jax.Array:
    """(k,) block weights c(V_i)."""
    return jax.ops.segment_sum(g.nw, labels, num_segments=k)


@jax.jit
def edge_cut(g: Graph, labels: jax.Array) -> jax.Array:
    """Total weight of cut edges (undirected; directed copies halved)."""
    return jnp.sum(_cut_weights(g, labels)) * 0.5


@jax.jit
def _cut_weights(g: Graph, labels: jax.Array) -> jax.Array:
    lu = labels[g.src]
    lv = labels[g.safe_col()]
    return jnp.where(g.edge_mask & (lu != lv), g.ew, 0.0)


CUT_CHUNK = 1024  # edges per fp32 partial sum of the reported cut


@jax.jit
def _chunk_sums(w: jax.Array) -> jax.Array:
    w = w.reshape(-1)
    w = jnp.pad(w, (0, (-w.shape[0]) % CUT_CHUNK))
    return jnp.sum(w.reshape(-1, CUT_CHUNK), axis=1)


def half_sum_exact(w: jax.Array) -> float:
    """Σ w / 2 as reported: fp32 partial sums over CUT_CHUNK-edge chunks on
    device, added in float64 on the host.  On integer weights every chunk
    sum stays far below 2²⁴, so the result is exact where one fp32
    accumulator over all edges is not (a directed total above 2²⁴, e.g.
    rmat_21's ~6.7e7)."""
    return telemetry.host_read(
        "cut", _chunk_sums(w),
        lambda c: float(np.sum(np.asarray(c, dtype=np.float64)) / 2.0))


def exact_edge_cut(g: Graph, labels: jax.Array) -> float:
    """The cut a result reports: :func:`edge_cut` with the total added in
    float64 (:func:`half_sum_exact`) — equal to a float64 recount of the
    labels on integer-weight graphs of any size."""
    return half_sum_exact(_cut_weights(g, labels))


@partial(jax.jit, static_argnames=("k",))
def imbalance(g: Graph, labels: jax.Array, k: int) -> jax.Array:
    """max_i c(V_i) / (c(V)/k) − 1."""
    bw = block_weights(g, labels, k)
    return jnp.max(bw) / (g.total_node_weight / k) - 1.0


@partial(jax.jit, static_argnames=("k",))
def total_overload(g: Graph, labels: jax.Array, k: int, lmax: jax.Array) -> jax.Array:
    """Σ_o max(0, c(V_o) − L_max) — the quantity Alg. 1 drives to zero."""
    bw = block_weights(g, labels, k)
    return jnp.sum(jnp.maximum(bw - lmax, 0.0))


# --------------------------------------------------------------------------
# Connectivity conn(v, V_j) — the partitioner's core primitive.
# Dense (n, k) formulation: one segment_sum over edge slots with key
# src·k + label[dst].  The Pallas kernel (kernels/gain) computes the same
# quantities tile-wise without materialising (n, k) in HBM.
# --------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("k",))
def conn_dense(g: Graph, labels: jax.Array, k: int) -> jax.Array:
    """(n, k) matrix of conn(v, V_j) = Σ_{(v,u)∈E, u∈V_j} ω(v,u)."""
    lv = labels[g.safe_col()]
    key = g.src * k + lv
    w = jnp.where(g.edge_mask, g.ew, 0.0)
    return jax.ops.segment_sum(w, key, num_segments=g.n * k).reshape(g.n, k)


@partial(jax.jit, static_argnames=("k",))
def best_moves(
    g: Graph,
    labels: jax.Array,
    k: int,
    capacity: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Per-vertex (own_conn, best_gain, best_target).

    ``capacity`` is an optional (k,) vector of remaining block capacity; a
    target j is eligible for vertex v iff capacity[j] ≥ c(v) (used by the
    rebalancer: capacity = L_max − c(V_u) for non-overloaded blocks, −inf
    otherwise).  With ``capacity=None`` every block except v's own is
    eligible (Jet move generation).

    best_gain = max_eligible_j conn(v,V_j) − conn(v,V_own); if no block is
    eligible, best_gain = −inf and best_target = own block.

    Move selection (the argmax + tie-break + no-eligible-block rule) is the
    shared :func:`repro.refine.gain.masked_best` — the same rule every gain
    backend of the unified refinement engine applies.
    """
    from repro.refine.gain import masked_best

    conn = conn_dense(g, labels, k)
    return masked_best(conn, labels, g.nw, capacity, k)
