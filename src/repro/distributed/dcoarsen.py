"""Distributed coarsening under ``shard_map`` (DESIGN.md §3).

The multilevel driver historically built the coarse hierarchy on the host;
this module moves both halves of a coarsening level into per-PE shard_map
bodies over mesh axis ``"pe"``, reusing djet.py's ghost-exchange pattern:

* **dcluster** — size-constrained LP clustering.  Per round: one all_gather
  of owned cluster labels (the ghost update), one psum of the per-cluster
  weight vector and one psum of the admission inflow (the size-cap
  bookkeeping).  Uniform draws happen in *global* vertex space
  (djet._global_uniform), so clustering takes bit-identical decisions on 1
  and on P devices — and identical to ``core.coarsen.cluster`` from the same
  key (exactly on integer-weight graphs, where every reduction is exact in
  fp32).
* **dcontract** — contraction with a *bucketed all_to_all edge reshuffle*:
  each PE relabels its local edges to coarse ids, buckets them by the coarse
  tail's new owner (contiguous blocks of ``blk = ceil(nc / P)`` coarse
  vertices per PE), and one ``all_to_all`` delivers every bucket.  The
  receiver coalesces parallel edges (sort + grouped segment reduction, the
  same pattern the clustering scoreboard uses) and emits its slice of the
  coarse :class:`ShardedGraph` — the coarse graph is *born sharded*; the
  fine graph is never gathered to the host.

Only scalars cross to the host: per level the cluster-weight cap, the
moved-vertex count of each LP round, nc and the max per-PE coarse edge count
— they pick the next level's static shapes, the BSP analogue of dKaMinPar's
global per-level synchronisation.  Each goes through
``repro.telemetry.host_read``, which names and counts it.  With
``halo=True`` a fourth scalar (h_local, the max per-PE interface count) rides
along and the hierarchy emits device-derived interface-only halo metadata
per level (``halo.halo_from_sharded``) — the halo V-cycle never gathers a
level graph either.

Coarse vertex layout: because each PE owns exactly ``blk`` coarse-vertex
slots, a coarse vertex's gathered-layout id equals its global id, so no dst
translation is needed after the reshuffle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import telemetry
from repro.core.coarsen import (
    grouped_best_cluster,
    joiner_mask,
    mask_joiner_targets,
)
from repro.core.graph import PAD
from repro.distributed.dgraph import ShardedGraph, owned_mask
from repro.distributed.djet import _gather, _global_uniform


# --------------------------------------------------------------------------
# per-PE shard_map bodies
# --------------------------------------------------------------------------

def dcluster_round_local(src, dst, ew, nw, owned, cl, gstart, key, cap,
                         *, P_: int, n_local: int, m_local: int, n_real: int):
    """One LP clustering round (core.coarsen.cluster_round, BSP form).

    ``cl`` holds cluster leader ids in *gathered layout* (owner·n_local +
    slot) — a strictly increasing function of global vertex id, so min-id
    tie-breaks agree with the host path.
    """
    n_pad = P_ * n_local
    cl_full = _gather(cl)
    # the round's coin flip in global vertex space, gathered so it can be
    # read at any cluster leader's gathered-layout id
    coin = _global_uniform(jax.random.fold_in(key, 1), gstart,
                           n_local=n_local, n_real=n_real)
    joiner = joiner_mask(coin)
    joiner_full = _gather(joiner)

    # best neighbouring cluster: the host path's grouped reduction, applied
    # to this PE's contiguous edge range (bit-identical group sums — the
    # local edge order is the host CSR order restricted to this PE)
    live = dst != PAD
    cl_dst = cl_full[jnp.where(live, dst, 0)]
    w = jnp.where(live, ew, 0.0)
    w = mask_joiner_targets(w, cl_dst, cl[src], joiner_full[cl_dst])
    best_cl, has, best_conn = grouped_best_cluster(
        src, cl_dst, w, n=n_local, m=m_local
    )
    best_cl = jnp.where(has, best_cl, cl).astype(jnp.int32)

    # cluster weights + in-expectation size-cap admission (one psum each)
    clw = jax.lax.psum(
        jax.ops.segment_sum(jnp.where(owned, nw, 0.0), cl, num_segments=n_pad),
        "pe",
    )
    want = joiner & (best_cl != cl) & (best_conn > 0) & owned
    want &= clw[best_cl] + nw <= cap
    inflow = jax.lax.psum(
        jax.ops.segment_sum(jnp.where(want, nw, 0.0), best_cl, num_segments=n_pad),
        "pe",
    )
    room = jnp.maximum(cap - clw, 0.0)
    p = jnp.where(inflow > 0, jnp.clip(room / jnp.maximum(inflow, 1e-9), 0.0, 1.0), 1.0)

    u = _global_uniform(key, gstart, n_local=n_local, n_real=n_real)
    accept = want & (u < p[best_cl])
    moved = jax.lax.psum(jnp.sum(accept.astype(jnp.int32)), "pe")
    return jnp.where(accept, best_cl, cl), moved


def dcompress_local(cl):
    """Leader path-compression ``cl = cl[cl]`` with one ghost gather."""
    cl_full = _gather(cl)
    return cl_full[cl]


# up to this many PEs the bucket rank is a counting sort (one cumulative
# count per PE, O(P·m) elementwise work and no device sort, whose compile
# dominates a level on the TPU); above it, one stable argsort, O(m log m)
COUNTING_SORT_MAX_P = 8


def bucket_rank(dest: jax.Array, P_: int) -> jax.Array:
    """Rank of each slot among the slots of its bucket, in slot order
    (``dest`` in [0, P_]; bucket ``P_`` is the dropped one and its ranks are
    unspecified).  The same ranks as a stable sort by ``dest``."""
    m = dest.shape[0]
    if P_ <= COUNTING_SORT_MAX_P:
        pos = jnp.zeros((m,), jnp.int32)
        for d in range(P_):
            in_d = dest == d
            pos = jnp.where(in_d, jnp.cumsum(in_d.astype(jnp.int32)) - 1, pos)
        return pos
    order = jnp.argsort(dest, stable=True)
    counts = jnp.zeros((P_ + 1,), jnp.int32).at[dest].add(1)
    starts = jnp.cumsum(counts) - counts
    rank = jnp.zeros((m,), jnp.int32).at[order].set(
        jnp.arange(m, dtype=jnp.int32))
    return rank - starts[dest]


def dcontract_local(src, dst, ew, nw, owned, cl,
                    *, P_: int, n_local: int, m_local: int, blk: int):
    """Contract the final clustering into the coarse sharded graph.

    Returns per-PE (src_c, dst_c, ew_c) padded to P·m_local slots (the
    driver slices them to the psum-maxed live count), the owned coarse
    weight slice, the fine→coarse mapping for uncoarsening, and the max
    per-PE coarse edge count.
    """
    n_pad = P_ * n_local
    pe = jax.lax.axis_index("pe")
    cl_full = _gather(cl)
    owned_full = _gather(owned)

    # coarse ids = rank of leader in gathered-id order (== global-id order)
    present = jnp.zeros((n_pad,), jnp.int32).at[cl_full].max(
        owned_full.astype(jnp.int32)
    )
    cid = (jnp.cumsum(present) - 1).astype(jnp.int32)

    # coarse node weights, dense over the P·blk coarse slot space (one psum)
    seg = jnp.where(owned, cid[cl], 0)
    nw_c_full = jax.lax.psum(
        jax.ops.segment_sum(jnp.where(owned, nw, 0.0), seg, num_segments=P_ * blk),
        "pe",
    )
    nw_c = jax.lax.dynamic_slice(nw_c_full, (pe * blk,), (blk,))
    map_loc = seg  # fine slot → global coarse id (0 on padding slots)

    # relabel local edges; drop intra-cluster edges
    live = dst != PAD
    cu = cid[cl[src]]
    cv = cid[cl_full[jnp.where(live, dst, 0)]]
    keep = live & (cu != cv)
    w = jnp.where(keep, ew, 0.0)

    # bucket by new owner of the coarse tail and pack the all_to_all buffer:
    # each edge goes to (dest, rank-within-bucket), the rank counted in slot
    # order.  A PE holds ≤ m_local live edges, so every bucket fits the
    # m_local-wide send row.
    dest = jnp.where(keep, cu // blk, P_)
    pos = bucket_rank(dest, P_)
    flat = jnp.where(dest < P_, dest * m_local + pos, P_ * m_local)
    send_cu = jnp.full((P_ * m_local,), -1, jnp.int32).at[flat].set(cu, mode="drop")
    send_cv = jnp.zeros((P_ * m_local,), jnp.int32).at[flat].set(cv, mode="drop")
    send_w = jnp.zeros((P_ * m_local,), jnp.float32).at[flat].set(w, mode="drop")

    shp = (P_, m_local)
    rcu = jax.lax.all_to_all(send_cu.reshape(shp), "pe", 0, 0, tiled=True).reshape(-1)
    rcv = jax.lax.all_to_all(send_cv.reshape(shp), "pe", 0, 0, tiled=True).reshape(-1)
    rw = jax.lax.all_to_all(send_w.reshape(shp), "pe", 0, 0, tiled=True).reshape(-1)

    # coalesce parallel edges: sort received slots by (row, head), grouped
    # segment sums, groups compacted to the front in CSR order (sorted by
    # head within each row — the same canonical order from_coo produces).
    # Empty slots sort last under an int32::max row; the sort is unstable
    # (inside a group it only reorders a fp32 sum, exact on integer weights)
    R = P_ * m_local
    imax = jnp.iinfo(jnp.int32).max
    valid = rcu >= 0
    row = jnp.where(valid, rcu - pe * blk, imax)
    colc = jnp.where(valid, rcv, imax)
    rowS, colS, wS = jax.lax.sort((row, colc, rw), num_keys=2,
                                  is_stable=False)
    vS = rowS != imax
    first = vS & jnp.concatenate(
        [jnp.array([True]), (rowS[1:] != rowS[:-1]) | (colS[1:] != colS[:-1])]
    )
    gidx = jnp.cumsum(first) - 1
    seg2 = jnp.where(vS, jnp.maximum(gidx, 0), R)
    wsum = jax.ops.segment_sum(jnp.where(vS, wS, 0.0), seg2, num_segments=R + 1)[:R]
    grow = jax.ops.segment_max(jnp.where(vS, rowS, -1), seg2, num_segments=R + 1)[:R]
    gcol = jax.ops.segment_max(jnp.where(vS, colS, -1), seg2, num_segments=R + 1)[:R]
    n_groups = jnp.sum(first.astype(jnp.int32))
    live_out = jnp.arange(R, dtype=jnp.int32) < n_groups
    src_c = jnp.where(live_out, grow, 0).astype(jnp.int32)
    # blk-sized contiguous blocks ⇒ gathered coarse id == global coarse id
    dst_c = jnp.where(live_out, gcol, PAD).astype(jnp.int32)
    ew_c = jnp.where(live_out, wsum, 0.0)
    mmax = jax.lax.pmax(n_groups, "pe")
    return src_c, dst_c, ew_c, nw_c, map_loc, mmax


# --------------------------------------------------------------------------
# shard_map factories (cached per mesh/shape)
# --------------------------------------------------------------------------

def _specs(n: int):
    return tuple([P("pe", None)] * n)


@functools.lru_cache(maxsize=128)
def _cluster_round_fn(mesh, P_: int, n_local: int, m_local: int, n_real: int):
    from repro.sharding.compat import shard_map

    def cluster_round(src, dst, ew, nw, owned, cl, gstart, key, cap):
        new_cl, moved = dcluster_round_local(
            src[0], dst[0], ew[0], nw[0], owned[0], cl[0], gstart[0], key, cap,
            P_=P_, n_local=n_local, m_local=m_local, n_real=n_real,
        )
        return new_cl[None], moved

    return jax.jit(shard_map(
        cluster_round, mesh=mesh,
        in_specs=_specs(6) + (P("pe"), P(), P()),
        out_specs=(P("pe", None), P()),
    ))


@functools.lru_cache(maxsize=128)
def _compress_fn(mesh, n_local: int):
    from repro.sharding.compat import shard_map

    def compress(cl):
        return dcompress_local(cl[0])[None]

    return jax.jit(shard_map(
        compress, mesh=mesh, in_specs=_specs(1), out_specs=P("pe", None)
    ))


@functools.lru_cache(maxsize=128)
def _contract_fn(mesh, P_: int, n_local: int, m_local: int, blk: int):
    from repro.sharding.compat import shard_map

    def contract(src, dst, ew, nw, owned, cl):
        src_c, dst_c, ew_c, nw_c, map_loc, mmax = dcontract_local(
            src[0], dst[0], ew[0], nw[0], owned[0], cl[0],
            P_=P_, n_local=n_local, m_local=m_local, blk=blk,
        )
        return src_c[None], dst_c[None], ew_c[None], nw_c[None], map_loc[None], mmax

    return jax.jit(shard_map(
        contract, mesh=mesh,
        in_specs=_specs(6),
        out_specs=_specs(5) + (P(),),
    ))


@functools.lru_cache(maxsize=128)
def _uncoarsen_fn(mesh, n_local_f: int, blk: int):
    from repro.sharding.compat import shard_map

    def uncoarsen(map_loc, owned, lab_c):
        lab_full = _gather(lab_c[0])
        out = jnp.where(owned[0], lab_full[map_loc[0]], 0)
        return out[None].astype(jnp.int32)

    return jax.jit(shard_map(
        uncoarsen, mesh=mesh, in_specs=_specs(3), out_specs=P("pe", None)
    ))


@functools.lru_cache(maxsize=128)
def _count_fn(n_pad: int):
    def count(cl_sh, owned_sh):
        present = jnp.zeros((n_pad,), jnp.int32).at[cl_sh.reshape(-1)].max(
            owned_sh.reshape(-1).astype(jnp.int32)
        )
        return jnp.sum(present)

    return jax.jit(count)


# --------------------------------------------------------------------------
# drivers (host control loop; only scalars cross the device boundary)
# --------------------------------------------------------------------------

def dcluster(mesh, sg: ShardedGraph, weight_cap: float, key,
             rounds: int = 5) -> jax.Array:
    """Sharded LP clustering; returns (P, n_local) leader ids in gathered
    layout.  Mirrors core.coarsen.cluster round-for-round (same key splits,
    same early-out on a zero moved-count)."""
    fn = _cluster_round_fn(mesh, sg.P, sg.n_local, sg.m_local, sg.n_real)
    owned = owned_mask(sg)
    # placed as the round's output is, so round 1 and rounds 2.. share one
    # compiled program (an unplaced first ``cl`` compiled the round twice)
    cl = jax.device_put(
        jnp.arange(sg.P * sg.n_local, dtype=jnp.int32).reshape(sg.P, sg.n_local),
        jax.sharding.NamedSharding(mesh, P("pe", None)))
    cap = jnp.asarray(weight_cap, jnp.float32)
    for _ in range(rounds):
        key, sub = jax.random.split(key)
        with telemetry.span("cluster_round"):
            telemetry.count_dispatch("cluster_round")
            cl, moved = fn(sg.src, sg.dst, sg.ew, sg.nw, owned, cl,
                           sg.vtx_start, sub, cap)
        if telemetry.host_read("moved", moved) == 0:
            break
    telemetry.count_dispatch("compress")
    return _compress_fn(mesh, sg.n_local)(cl)


def dcontract(mesh, sg: ShardedGraph, cl) -> tuple[ShardedGraph, jax.Array, int]:
    """Sharded contraction; returns (coarse_sharded, map_sh, nc).

    ``map_sh`` is (P, n_local_fine): global coarse id of each owned fine
    slot (labels project down as one gather in :func:`duncoarsen`).
    """
    owned = owned_mask(sg)
    telemetry.count_dispatch("count")
    nc = telemetry.host_read("nc", _count_fn(sg.n_pad)(cl, owned))
    blk = max(1, -(-nc // sg.P))  # coarse vertices per PE (static next-shape)

    fn = _contract_fn(mesh, sg.P, sg.n_local, sg.m_local, blk)
    with telemetry.span("contract"):
        telemetry.count_dispatch("contract")
        src_c, dst_c, ew_c, nw_c, map_sh, mmax = fn(
            sg.src, sg.dst, sg.ew, sg.nw, owned, cl
        )
    m_local_c = max(1, telemetry.host_read("mmax", mmax))
    coarse = ShardedGraph(
        src=src_c[:, :m_local_c],
        dst=dst_c[:, :m_local_c],
        ew=ew_c[:, :m_local_c],
        nw=nw_c,
        vtx_start=jnp.asarray(
            np.minimum(np.arange(sg.P, dtype=np.int64) * blk, nc).astype(np.int32)
        ),
        n_real=nc,
        P=sg.P,
        n_local=blk,
        m_local=m_local_c,
    )
    return coarse, map_sh, nc


def duncoarsen(mesh, fine_sg: ShardedGraph, map_sh, coarse_sg: ShardedGraph,
               lab_sh):
    """Project coarse labels to the finer level: one all_gather of the coarse
    label slices, then a per-PE gather through the fine→coarse mapping."""
    owned = owned_mask(fine_sg)
    with telemetry.span("uncoarsen"):
        telemetry.count_dispatch("uncoarsen")
        return _uncoarsen_fn(mesh, fine_sg.n_local, coarse_sg.n_local)(
            map_sh, owned, lab_sh
        )


def dcoarsen_hierarchy(
    mesh,
    sg0: ShardedGraph,
    k: int,
    key,
    coarsen_until: int | None = None,
    max_levels: int = 30,
    shrink_min: float = 0.05,
    halo: bool = False,
):
    """Sharded analogue of core.coarsen.coarsen_hierarchy.

    Returns (levels, coarsest) where levels is a list of
    (fine_sharded, map_sh, coarse_sharded) from finest to coarsest-1.

    With ``halo=True`` the hierarchy additionally emits the interface-only
    halo metadata of every level *derived from the sharded level itself*
    (``halo.halo_from_sharded`` — a per-PE device-side construction; only
    the h_local scalar joins the 3 per-level scalars that already cross to
    the host): returns (levels, coarsest, halos) where ``halos[i]`` is the
    :class:`~repro.distributed.halo.HaloShardedGraph` of ``levels[i][0]``
    and ``halos[-1]`` that of the coarsest graph.
    """
    if coarsen_until is None:
        coarsen_until = max(512, 16 * k)
    total_w = telemetry.host_read("total_w", jnp.sum(sg0.nw), float)
    levels = []
    cur = sg0
    while cur.n_real > coarsen_until and len(levels) < max_levels:
        with telemetry.span("coarsen/level", level=len(levels),
                            n=cur.n_real, m=cur.P * cur.m_local):
            # max cluster weight: a cluster must never exceed what fits a
            # block
            cap = max(total_w / coarsen_until,
                      telemetry.host_read("cap", jnp.max(cur.nw), float))
            key, sub = jax.random.split(key)
            cl = dcluster(mesh, cur, cap, sub)
            coarse, map_sh, nc = dcontract(mesh, cur, cl)
        if nc >= (1.0 - shrink_min) * cur.n_real:
            break  # diminishing returns — stop coarsening
        levels.append((cur, map_sh, coarse))
        cur = coarse
    if not halo:
        return levels, cur
    from repro.distributed.halo import halo_from_sharded

    halos = [halo_from_sharded(mesh, sg) for sg, _, _ in levels]
    halos.append(halo_from_sharded(mesh, cur))
    return levels, cur, halos
