"""Initial partitioning of the coarsest graph.

dKaMinPar computes initial partitions by deep-multilevel bisection on a
replicated coarsest graph.  Here: multi-restart greedy balanced seeding
(heaviest vertex → lightest block) followed by a strong refinement pass with
the paper's own machinery (Jet + rebalance); best balanced cut wins.  The
coarsest graph is tiny (≤ max(512, 16k) vertices) so restarts are cheap.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro import telemetry
from repro.core.graph import Graph
from repro.core.partition import edge_cut, l_max, total_overload


def greedy_seed_arith(nw: jax.Array, k: int, key: jax.Array) -> jax.Array:
    """Traceable body of :func:`greedy_balanced_seed` — the ONE copy of the
    seeding arithmetic, shared by the jitted solo entry point below and the
    batched initial-partition program (``repro.refine.drivers``), so the
    two paths are bit-identical by construction.

    Assign vertices (heaviest first, random tie order) to the currently
    lightest block — an LPT-style balanced seeding.

    The tie-break noise is the engine's per-vertex ``tid_uniform`` stream
    (a pure function of (key, id)), NOT a ``uniform(key, (n,))`` draw:
    threefry is not prefix-stable across shapes, and the batched engine
    runs this seeding on pad-to-bucket graphs — the noise must not change
    when padding slots are appended (DESIGN.md §2).  Padding slots carry
    nw = 0 and noise < 1e-3, so they sort strictly after every real vertex
    (nw ≥ 1) and their zero-weight block additions are no-ops."""
    from repro.refine.comm import tid_uniform

    n = nw.shape[0]
    noise = tid_uniform(key, jnp.arange(n, dtype=jnp.int32), maxval=1e-3)
    order = jnp.argsort(-(nw + noise))

    def body(i, carry):
        labels, bw = carry
        v = order[i]
        b = jnp.argmin(bw).astype(jnp.int32)
        labels = labels.at[v].set(b)
        bw = bw.at[b].add(nw[v])
        return labels, bw

    labels0 = jnp.zeros(n, dtype=jnp.int32)
    bw0 = jnp.zeros(k, dtype=jnp.float32)
    labels, _ = jax.lax.fori_loop(0, n, body, (labels0, bw0))
    return labels


greedy_balanced_seed = partial(jax.jit, static_argnames=("k",))(
    greedy_seed_arith)


def initial_partition(
    g: Graph,
    k: int,
    eps: float,
    key: jax.Array,
    n_restarts: int = 4,
) -> jax.Array:
    # local import to avoid a cycle (refine drives initial partitioning too)
    from repro.core.refine import jet_refine

    lmax = l_max(g, k, eps)
    best_labels, best_cut = None, float("inf")
    for restart in range(n_restarts):
        with telemetry.span("init/restart", restart=restart):
            key, k1, k2 = jax.random.split(key, 3)
            labels = greedy_balanced_seed(g.nw, k, k1)
            labels = jet_refine(g, labels, k, eps, k2, rounds=2, patience=6,
                                max_inner=24)
            cut = telemetry.host_read("init_cut", edge_cut(g, labels), float)
            ov = telemetry.host_read(
                "init_ov", total_overload(g, labels, k, lmax), float)
        if ov <= 0 and cut < best_cut:
            best_labels, best_cut = labels, cut
    if best_labels is None:  # all restarts imbalanced — take the last anyway
        best_labels = labels
    return best_labels
