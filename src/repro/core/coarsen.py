"""Multilevel coarsening: size-constrained label-propagation clustering +
graph contraction (the dKaMinPar coarsening scheme the paper builds on).

Clustering runs on device (jit) without materialising an (n, n_clusters)
table: per-vertex best-neighbouring-cluster is computed by lexsorting edge
(src, cluster[dst]) pairs and doing grouped reductions — the sparse analogue
of ``conn_dense`` that works when the "number of blocks" is Θ(n).

Contraction is a host-side (numpy) data-management step: level sizes are
data-dependent, so the multilevel driver is a host loop anyway (dKaMinPar
synchronises globally per level as well).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.core.graph import Graph, from_coo


def grouped_best_cluster(src, cl_dst, w, *, n: int, m: int):
    """Array-level core of the LP scoring: per tail vertex, the strongest
    neighbouring cluster over (src, cluster[dst]) groups.

    Grouped reduction over (src, cluster)-sorted pairs; ties broken by
    smallest cluster id (determinism).  Shared bit-for-bit by the host path
    below and the per-PE sharded path (distributed/dcoarsen.py) — the
    sharded==host equivalence tests depend on both calling exactly this.
    The sort is unstable — the order inside a group only changes the order
    of a fp32 sum, exact on integer weights — because a stable 2-key TPU
    sort compiles for minutes at 2^20 slots.

    Returns (best_cl, has, best_conn); ``best_cl`` is int32::max where a
    vertex has no live group (caller substitutes its current cluster).
    """
    src_s, cl_s, w_s = jax.lax.sort(
        (src.astype(jnp.int32), cl_dst.astype(jnp.int32), w), num_keys=2,
        is_stable=False)

    first = jnp.concatenate(
        [jnp.array([True]), (src_s[1:] != src_s[:-1]) | (cl_s[1:] != cl_s[:-1])]
    )
    gid = jnp.cumsum(first) - 1  # group id per sorted edge, groups ≤ m

    gsum = jax.ops.segment_sum(w_s, gid, num_segments=m)
    gsrc = jax.ops.segment_max(jnp.where(first, src_s, -1), gid, num_segments=m)
    gcl = jax.ops.segment_max(jnp.where(first, cl_s, -1), gid, num_segments=m)
    gsrc_safe = jnp.maximum(gsrc, 0)

    vmax = jax.ops.segment_max(gsum, gsrc_safe, num_segments=n)
    vmax = jnp.where(jnp.isfinite(vmax), vmax, 0.0)

    # among groups achieving the max, pick the smallest cluster id (determinism)
    is_best = (gsum >= vmax[gsrc_safe]) & (gsrc >= 0)
    cand_cl = jnp.where(is_best, gcl, jnp.iinfo(jnp.int32).max)
    best_cl = jax.ops.segment_min(cand_cl, gsrc_safe, num_segments=n)
    has = best_cl != jnp.iinfo(jnp.int32).max
    return best_cl, has, vmax


def joiner_mask(coin):
    """The round's coin flip: ``True`` for vertices that may join another
    cluster this round, ``False`` for *receivers*, which stay put and may
    be joined.  ``coin`` is the round's second global-vertex-space draw
    (``fold_in(key, 1)``).  Without it every vertex of a uniform mesh moves
    to its smallest-id neighbour at once, the moves chase each other, and
    a 2^16-vertex grid shrinks by < 5 % per level — the V-cycle stopped at
    one level (DESIGN.md §3)."""
    return coin < 0.5


def mask_joiner_targets(w, cl_dst, cl_src, joiner_dst):
    """Zero the weight of edges into *other* clusters whose leader is a
    joiner this round, so the best-cluster search only sees receivers (and
    the vertex's own cluster, at full weight)."""
    return jnp.where(joiner_dst & (cl_dst != cl_src), 0.0, w)


@partial(jax.jit, static_argnames=())
def cluster_round(
    g: Graph,
    cluster: jax.Array,
    cl_weight_cap: jax.Array,
    key: jax.Array,
):
    """One LP clustering round with probabilistic size-cap admission: only
    this round's joiners move, and only into receivers' clusters
    (:func:`joiner_mask`)."""
    joiner = joiner_mask(jax.random.uniform(jax.random.fold_in(key, 1),
                                            (g.n,)))
    cl_dst = cluster[g.safe_col()]
    w = jnp.where(g.edge_mask, g.ew, 0.0)
    w = mask_joiner_targets(w, cl_dst, cluster[g.src], joiner[cl_dst])
    best_cl, has, best_conn = grouped_best_cluster(g.src, cl_dst, w, n=g.n,
                                                   m=g.m)
    best_cl = jnp.where(has, best_cl, cluster).astype(jnp.int32)
    cl_w = jax.ops.segment_sum(g.nw, cluster, num_segments=g.n)
    want = joiner & (best_cl != cluster) & (best_conn > 0)
    want &= cl_w[best_cl] + g.nw <= cl_weight_cap

    # in-expectation cap: admit into cluster c with prob room_c / inflow_c
    inflow = jax.ops.segment_sum(jnp.where(want, g.nw, 0.0), best_cl, num_segments=g.n)
    room = jnp.maximum(cl_weight_cap - cl_w, 0.0)
    p = jnp.where(inflow > 0, jnp.clip(room / jnp.maximum(inflow, 1e-9), 0.0, 1.0), 1.0)
    accept = want & (jax.random.uniform(key, (g.n,)) < p[best_cl])
    return jnp.where(accept, best_cl, cluster), jnp.sum(accept)


def cluster(
    g: Graph,
    weight_cap: float,
    key: jax.Array,
    rounds: int = 5,
) -> jax.Array:
    """Run a few clustering rounds; returns (n,) cluster leader ids."""
    cl = jnp.arange(g.n, dtype=jnp.int32)
    cap = jnp.asarray(weight_cap, jnp.float32)
    for _ in range(rounds):
        key, sub = jax.random.split(key)
        with telemetry.span("cluster_round"):
            telemetry.count_dispatch("cluster_round")
            cl, moved = cluster_round(g, cl, cap, sub)
        if telemetry.host_read("moved", moved) == 0:
            break
    # path-compress: follow leader once (LP may chain v→u→w between rounds)
    cl = cl[cl]
    return cl


def contract_arrays(cluster, nw, src, col, ew):
    """Pure contraction arithmetic (host/numpy), shared by :func:`contract`
    and the sharded path's reference/reconstruction helpers (distributed/
    dcoarsen.py — the device implementation computes the same quantities
    under shard_map and is tested for bit-equality against this).

    ``src``/``col``/``ew`` are the *live* directed edges.  Returns
    ``(nc, mapping, nw_c, cu, cv, w)`` where mapping relabels vertices to
    coarse ids (= rank of their cluster leader) and (cu, cv, w) are the
    surviving inter-cluster directed edges, **not** yet coalesced.
    """
    cl = np.asarray(cluster, dtype=np.int64)
    uniq, mapping = np.unique(cl, return_inverse=True)
    nc = int(len(uniq))

    nw_c = np.zeros(nc, dtype=np.float32)
    np.add.at(nw_c, mapping, np.asarray(nw))

    cu = mapping[np.asarray(src)]
    cv = mapping[np.asarray(col)]
    w = np.asarray(ew)
    keep = cu != cv  # intra-cluster edges vanish
    return nc, mapping, nw_c, cu[keep], cv[keep], w[keep]


def contract(g: Graph, cluster) -> tuple[Graph, jax.Array]:
    """Contract clusters into a coarse graph.  Host-side numpy.

    Returns (coarse_graph, mapping) with ``mapping[v] = coarse id of v`` so
    label projection during uncoarsening is ``labels_fine = labels_coarse[mapping]``.
    """
    live = np.asarray(g.edge_mask)
    nc, mapping, nw_c, cu, cv, w = contract_arrays(
        cluster,
        g.nw,
        np.asarray(g.src)[live],
        np.asarray(g.safe_col())[live],
        np.asarray(g.ew)[live],
    )

    # coalesce parallel edges; from_coo would double them if we symmetrised,
    # but (cu, cv) already contains both directions — keep as directed COO.
    coarse = from_coo(nc, cu, cv, w, nw=nw_c, symmetrize=False)
    return coarse, jnp.asarray(mapping.astype(np.int32))


def coarsen_hierarchy(
    g: Graph,
    k: int,
    key: jax.Array,
    coarsen_until: int | None = None,
    max_levels: int = 30,
    shrink_min: float = 0.05,
):
    """Iteratively coarsen; returns (levels, coarsest) where levels is a list
    of (fine_graph, mapping) from finest to coarsest-1."""
    if coarsen_until is None:
        coarsen_until = max(512, 16 * k)
    total_w = telemetry.host_read("total_w", g.total_node_weight, float)
    levels = []
    cur = g
    while cur.n > coarsen_until and len(levels) < max_levels:
        with telemetry.span("coarsen/level", level=len(levels), n=cur.n,
                            m=cur.m):
            # max cluster weight: a cluster must never exceed what fits a
            # block
            cap = max(total_w / coarsen_until, telemetry.host_read(
                "cap", cur.nw, lambda nw: float(np.asarray(nw).max())))
            key, sub = jax.random.split(key)
            cl = cluster(cur, cap, sub)
            with telemetry.span("contract"):
                coarse, mapping = contract(cur, cl)
        if coarse.n >= (1.0 - shrink_min) * cur.n:
            break  # diminishing returns — stop coarsening
        levels.append((cur, mapping))
        cur = coarse
    return levels, cur
