"""Block-sharded graph for the distributed partitioner.

Model (paper §2): PEs 1..P each own a *contiguous* range of vertices with
roughly the same number of edges per PE; undirected edges are stored as two
directed copies with the tail's owner; remote endpoints are ghost vertices —
each PE knows the block id of every ghost (here: the label array of owned
vertices is all-gathered each round, the BSP analogue of the ghost update —
see DESIGN.md §2 for the halo=interface variant).

Layout (leading axis = PE, sharded over mesh axis "pe" by shard_map):

  src   (P, m_local) int32 — *local* row index of the tail (0..n_local)
  dst   (P, m_local) int32 — head id in *gathered layout* (see below); PAD pad
  ew    (P, m_local) f32
  nw    (P, n_local) f32   — weights of owned vertices (0 on padding)
  n_local, m_local, n_pad = P·n_local static.

Gathered layout: after ``all_gather`` of the (n_local,) per-PE label slices
the full label array has shape (P·n_local,) with PE p's owned vertex i at
position p·n_local + i.  ``dst`` is pre-translated into this coordinate
system at shard time so the ghost lookup is a single gather per round.

The vertex split is chosen to equalise *edges* per PE (the paper's layout):
a prefix-sum split of the degree array into P roughly-equal-weight ranges,
then each range padded to common n_local / m_local.

``shard_graph`` is the single home of this split: the interface-only halo
layout (``distributed.halo``) is *derived* from a :class:`ShardedGraph` —
per-PE on device for the sharded V-cycle, or via the same layout-pure core
at setup time for host-built levels — never from its own split of the
centralised graph.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import PAD, Graph, from_coo


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ShardedGraph:
    src: jax.Array   # (P, m_local) local row ids
    dst: jax.Array   # (P, m_local) global head ids, PAD on padding
    ew: jax.Array    # (P, m_local)
    nw: jax.Array    # (P, n_local)
    vtx_start: jax.Array  # (P,) global id of each PE's first owned vertex
    n_real: int = dataclasses.field(metadata=dict(static=True))
    P: int = dataclasses.field(metadata=dict(static=True))
    n_local: int = dataclasses.field(metadata=dict(static=True))
    m_local: int = dataclasses.field(metadata=dict(static=True))

    @property
    def n_pad(self) -> int:
        return self.P * self.n_local

    @property
    def total_node_weight(self):
        return jnp.sum(self.nw)


def shard_plan(row_ptr: np.ndarray, n: int, P: int):
    """The edge-balanced contiguous vertex split, from ``row_ptr`` alone.

    Returns ``(starts, n_local, m_local)`` with ``starts`` of length P+1.
    The single home of the split arithmetic: :func:`shard_graph` (in-memory
    path) and ``repro.graphs.ingest.ingest_sharded`` (out-of-core chunked
    path) both call it, which is what makes the two paths bit-identical by
    construction — the chunked ingest needs only this O(n) plan plus one
    chunk of edges resident at a time."""
    row_ptr = np.asarray(row_ptr, dtype=np.int64)
    m_live = int(row_ptr[-1])

    # contiguous ranges with ~equal edges: cut at multiples of m/P
    targets = (np.arange(1, P) * m_live) / P
    cuts = np.searchsorted(row_ptr[1:], targets, side="left") + 1
    starts = np.concatenate([[0], cuts, [n]]).astype(np.int64)
    starts = np.maximum.accumulate(starts)  # guard degenerate graphs

    n_local = int(np.max(np.diff(starts))) if P > 0 else n
    n_local = max(1, n_local)
    m_per = [int(row_ptr[starts[p + 1]] - row_ptr[starts[p]]) for p in range(P)]
    m_local = max(1, max(m_per))
    return starts, n_local, m_local


def gathered_ids(heads: np.ndarray, owner_starts: np.ndarray,
                 n_local: int) -> np.ndarray:
    """Translate global head ids → gathered-layout ids
    (owner·n_local + offset) — shared by shard_graph and chunked ingest."""
    owner = np.searchsorted(owner_starts, heads, side="right") - 1
    return owner * n_local + (heads - owner_starts[owner])


def shard_graph(g: Graph, P: int, mesh=None) -> ShardedGraph:
    """Host-side partition of ``g`` into P contiguous, edge-balanced ranges.

    With ``mesh`` (a 1-D ``"pe"`` mesh of P devices) every (P, ...) array
    goes from the host straight to its PE's device — no whole-graph copy
    lands on the first device on the way; without it the arrays take the
    default device and the level programs re-shard them."""
    row_ptr = np.asarray(g.row_ptr, dtype=np.int64)
    starts, n_local, m_local = shard_plan(row_ptr, g.n, P)

    src = np.zeros((P, m_local), dtype=np.int32)
    dst = np.full((P, m_local), int(PAD), dtype=np.int32)
    ew = np.zeros((P, m_local), dtype=np.float32)
    nw = np.zeros((P, n_local), dtype=np.float32)

    col = np.asarray(g.col)
    gsrc = np.asarray(g.src)
    gew = np.asarray(g.ew)
    gnw = np.asarray(g.nw)

    # translate global head ids → gathered-layout ids (owner·n_local + offset)
    owner_starts = starts[:P]
    def to_gathered(v: np.ndarray) -> np.ndarray:
        return gathered_ids(v, owner_starts, n_local)

    for p in range(P):
        v0, v1 = starts[p], starts[p + 1]
        e0, e1 = int(row_ptr[v0]), int(row_ptr[v1])
        cnt = e1 - e0
        src[p, :cnt] = gsrc[e0:e1] - v0
        heads = col[e0:e1]
        live = heads != int(PAD)
        dst[p, :cnt][live] = to_gathered(heads[live].astype(np.int64))
        ew[p, :cnt] = gew[e0:e1]
        nw[p, : v1 - v0] = gnw[v0:v1]

    if mesh is None:
        put = jnp.asarray
    else:
        sharding = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec("pe"))
        put = lambda x: jax.device_put(x, sharding)  # noqa: E731
    return ShardedGraph(
        src=put(src),
        dst=put(dst),
        ew=put(ew),
        nw=put(nw),
        vtx_start=put(starts[:P].astype(np.int32)),
        n_real=g.n,
        P=P,
        n_local=n_local,
        m_local=m_local,
    )


def labels_to_sharded(sg: ShardedGraph, labels: jax.Array) -> jax.Array:
    """(n,) global labels → (P, n_local) owner-sharded layout (host/setup)."""
    starts = np.asarray(sg.vtx_start, dtype=np.int64)
    lab = np.asarray(labels)
    out = np.zeros((sg.P, sg.n_local), dtype=np.int32)
    for p in range(sg.P):
        v0 = starts[p]
        v1 = starts[p + 1] if p + 1 < sg.P else sg.n_real
        out[p, : v1 - v0] = lab[v0:v1]
    return jnp.asarray(out)


def labels_from_sharded(sg: ShardedGraph, lab_sh: jax.Array) -> jax.Array:
    """(P, n_local) → (n,) global labels (host/extraction)."""
    starts = np.asarray(sg.vtx_start, dtype=np.int64)
    lab = np.asarray(lab_sh)
    out = np.zeros(sg.n_real, dtype=np.int32)
    for p in range(sg.P):
        v0 = starts[p]
        v1 = starts[p + 1] if p + 1 < sg.P else sg.n_real
        out[v0:v1] = lab[p, : v1 - v0]
    return jnp.asarray(out)


def sharded_to_graph(sg: ShardedGraph) -> Graph:
    """Host-side inverse of :func:`shard_graph`: gather a (small) sharded
    graph back into a :class:`Graph`.

    Only used where dKaMinPar also centralises — the coarsest graph handed to
    initial partitioning, and test reconstruction.  Produces the bit-same
    Graph as building the level on the host (``from_coo`` canonicalises edge
    order), which is what makes the sharded and host coarsening paths
    interchangeable mid-V-cycle.
    """
    starts = np.asarray(sg.vtx_start, dtype=np.int64)
    ends = np.concatenate([starts[1:], [sg.n_real]])
    src_sh = np.asarray(sg.src)
    dst_sh = np.asarray(sg.dst)
    ew_sh = np.asarray(sg.ew)
    nw_sh = np.asarray(sg.nw)

    nw = np.zeros(sg.n_real, dtype=np.float32)
    us, vs, ws = [], [], []
    for p in range(sg.P):
        width = int(ends[p] - starts[p])
        nw[starts[p]:ends[p]] = nw_sh[p, :width]
        live = dst_sh[p] != int(PAD)
        if not live.any():
            continue
        d = dst_sh[p][live].astype(np.int64)
        owner = d // sg.n_local
        heads = starts[owner] + (d - owner * sg.n_local)
        us.append(starts[p] + src_sh[p][live].astype(np.int64))
        vs.append(heads)
        ws.append(ew_sh[p][live])
    if us:
        u = np.concatenate(us)
        v = np.concatenate(vs)
        w = np.concatenate(ws)
    else:
        u = np.zeros(0, np.int64)
        v = np.zeros(0, np.int64)
        w = np.zeros(0, np.float32)
    return from_coo(sg.n_real, u, v, w, nw=nw, symmetrize=False)


def sharded_edge_cut(sg: ShardedGraph, lab_sh: jax.Array) -> float:
    """Edge cut from the sharded layout alone (no host Graph needed — the
    out-of-core ingest path's metric).  ``lab_sh`` is (P, n_local)
    owner-sharded labels; each undirected edge is stored as two directed
    copies, so the masked sum halves like ``core.partition.exact_edge_cut``
    (bit-equal on integer weights; summation order may differ otherwise)."""
    from repro.core.partition import half_sum_exact

    return half_sum_exact(_sharded_cut_weights(sg, lab_sh))


@jax.jit
def _sharded_cut_weights(sg: ShardedGraph, lab_sh: jax.Array) -> jax.Array:
    lab_g = lab_sh.reshape(-1)  # gathered layout: PE p's vertex i at p·n_local+i
    src_lab = jnp.take_along_axis(lab_sh, sg.src, axis=1)
    live = sg.dst != PAD
    dst_lab = lab_g[jnp.where(live, sg.dst, 0)]
    return jnp.where(live & (src_lab != dst_lab), sg.ew, 0.0)


def sharded_imbalance(sg: ShardedGraph, lab_sh: jax.Array, k: int):
    """Imbalance from the sharded layout (padding slots weigh 0, so they
    contribute nothing to the block weights)."""
    bw = jax.ops.segment_sum(sg.nw.reshape(-1),
                             lab_sh.reshape(-1).astype(jnp.int32),
                             num_segments=k)
    return jnp.max(bw) / (jnp.sum(sg.nw) / k) - 1.0


def owned_mask(sg: ShardedGraph) -> jax.Array:
    """(P, n_local) bool — True where the slot holds a real owned vertex."""
    starts = np.asarray(sg.vtx_start, dtype=np.int64)
    ends = np.concatenate([starts[1:], [sg.n_real]])
    idx = np.arange(sg.n_local)[None, :]
    return jnp.asarray(idx < (ends - starts)[:, None])
