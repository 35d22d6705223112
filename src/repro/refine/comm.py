"""Comm backends of the unified refinement engine (DESIGN.md §2).

Every phase of refinement — Jet move generation, the afterburner, the
probabilistic and greedy rebalancers, LP — needs exactly four communication
primitives, regardless of how the graph is laid out:

  * ``exchange``  — publish a per-owned-vertex field so edge heads can read
    it (the paper's ghost update; labels, gains, targets, ∈M flags);
  * ``lookup``    — read the exchanged field at every edge head;
  * ``psum``      — all-reduce a replicated reduction (block weights, bucket
    matrix, candidate inflow, cut/overload scalars);
  * ``gather``    — concatenate a small per-PE vector on every PE (the
    greedy rebalancer's candidate records).

plus two layout-aware helpers: ``uniform`` (per-vertex randomness keyed on
*global* vertex ids — :func:`tid_uniform` — so decisions are P-, padding-
and batch-invariant) and ``apply_moves``
(scatter the greedy rebalancer's replayed global move list back onto owned
slots).  Three backends implement the protocol:

  * :class:`SingleComm`    — single device; every primitive is the identity.
  * :class:`AllGatherComm` — the baseline BSP protocol: ``exchange`` is one
    ``all_gather`` of the full owned slice in gathered layout
    (``dgraph.ShardedGraph``).
  * :class:`HaloComm`      — interface-only exchange: ``exchange`` gathers
    ``x[:h_local]`` (``halo.HaloShardedGraph``); heads carry halo codes.

The engine arithmetic (``engine.py``) is written once against this protocol;
a gain backend × comm backend × P choice never changes the move sequence
(the determinism contract, tested in tests/test_refine_matrix.py).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

class EdgeView(NamedTuple):
    """Per-PE static view of one refinement level.

    ``head`` is the per-edge head id in the *backend coordinate system*
    (local vertex id / gathered-layout id / halo code); ``head_tid`` and
    ``my_tid`` are tie-break ids, order-isomorphic to global vertex ids in
    every backend, so deterministic tie-breaks agree across backends.
    """

    src: jax.Array       # (m,) local row id of the tail
    head: jax.Array      # (m,) head id in backend coordinates
    live: jax.Array      # (m,) bool — non-padding edge slots
    ew: jax.Array        # (m,) edge weights (0 on padding)
    head_tid: jax.Array  # (m,) tie-break id of the head
    my_tid: jax.Array    # (n_local,) tie-break id of each owned slot
    nw: jax.Array        # (n_local,) vertex weights (0 on padding)
    owned: jax.Array     # (n_local,) bool — real owned vertices

    @property
    def n_local(self) -> int:
        return self.nw.shape[0]

    # ---- the edge-layout protocol of the afterburner ----------------------
    # (the m-slot layout; :class:`DenseView` is the (D, N) one)

    def heads(self, cm, x):
        """Per-edge value of the owned field ``x`` at each edge's head."""
        return cm.lookup(self, cm.exchange(x), x)

    def tails(self, x):
        """Per-edge value of the owned field ``x`` at each edge's tail."""
        return x[self.src]

    def rowsum(self, x_e):
        """Per-owned-vertex sum of a per-edge value over the vertex's row."""
        return jax.ops.segment_sum(x_e, self.src, num_segments=self.n_local)

    def relayout(self, x_e):
        """A per-slot array in this layout (the identity here)."""
        return x_e

    def head_moves(self, cm, target, cand, gmask=None):
        """Each head's (target, ∈M, masked gain) — one lookup per field;
        ``gmask`` None skips the gain."""
        tu = self.heads(cm, target)
        cu = self.heads(cm, cand)
        gu = None if gmask is None else self.heads(cm, gmask)
        return tu, cu, gu


class DenseView(NamedTuple):
    """The (D, N) neighbour-major layout of one level's edges: entry
    ``[r, v]`` is v's r-th live edge, the layout the Pallas gain kernel
    reads.  Built once per level from the gain backend's ``eslot`` table
    (:meth:`repro.refine.gain.PallasGain.__init__`); it answers the same
    edge-layout protocol as :class:`EdgeView`, with a tail value as a
    broadcast row and a row sum as a column sum — no gather or scatter
    over the m slots.  Dead entries (rows past a vertex's degree, columns
    past ``n_local``) carry weight 0 and head 0, a valid id in every comm
    backend, so they read garbage that weighs nothing.
    """

    eslot: jax.Array     # (D, N) edge slot of each entry (m = dead)
    head: jax.Array      # (D, N) head id in backend coordinates (0 = dead)
    live: jax.Array      # (D, N) bool
    ew: jax.Array        # (D, N) edge weights (0 on dead entries)
    head_tid: jax.Array  # (D, N) tie-break id of the head
    my_tid: jax.Array    # (n_local,) tie-break id of each owned slot

    @property
    def n_local(self) -> int:
        return self.my_tid.shape[0]

    heads = EdgeView.heads

    def tails(self, x):
        pad = self.eslot.shape[1] - x.shape[0]
        return jnp.pad(x, (0, pad))[None, :]

    def rowsum(self, x_e):
        return jnp.sum(x_e, axis=0)[: self.n_local]

    def relayout(self, x_e):
        from repro.core.graph import PAD  # deferred: core↔refine cycle

        return slot_table(self.eslot, x_e, PAD)

    def head_moves(self, cm, target, cand, gmask=None):
        """∈M folds into the target: a non-candidate's target is looked up
        as −1, so two lookups or one, not three.  Each looked-up field is
        then computed just before its lookup, and XLA keeps such a short-
        lived table in VMEM; a (D, N) lookup from a table in HBM (``target``
        as the gain kernel left it) took 2.2 times as long on a v5e."""
        tu = self.heads(cm, jnp.where(cand, target, -1))
        gu = None if gmask is None else self.heads(cm, gmask)
        return tu, tu >= 0, gu


def slot_table(eslot, x_e, fill):
    """(D, N) table of a per-slot array through ``eslot``; ``fill`` where
    the entry is dead (``eslot == m``)."""
    return jnp.concatenate([x_e, jnp.full((1,), fill, x_e.dtype)])[eslot]


def dense_view(ev: EdgeView, eslot, nbr_w) -> DenseView:
    """The :class:`DenseView` of ``ev`` through its ``eslot`` table."""
    return DenseView(eslot=eslot, head=slot_table(eslot, ev.head, 0),
                     live=eslot < ev.src.shape[0], ew=nbr_w,
                     head_tid=slot_table(eslot, ev.head_tid, 0),
                     my_tid=ev.my_tid)


def tid_uniform(key, tid, maxval: float = 1.0):
    """THE per-vertex uniform stream of the refinement engine: one value per
    *global vertex id*, ``u(v) = uniform(fold_in(key, v))``.

    A pure function of ``(key, id)`` — unlike a ``uniform(key, (n,))`` draw
    (threefry is not prefix-stable across shapes), the stream is invariant
    under resharding, padding and batching: every backend (single device,
    all-gather BSP, halo, and the vmapped pad-to-bucket batched engine)
    reads the identical value for a given real vertex no matter how many
    padding slots or batch neighbours surround it.  This is what lets
    ``partition_batch``'s B=1 path be bit-identical to ``partition`` and a
    graph's labels be independent of its bucket mates (DESIGN.md §2).
    Formerly the halo backend's ``uniform_mode="fold"`` scale stream — now
    the one canonical stream (the shape-dependent global draw is retired
    from refinement; coarsening keeps its own, see ``global_uniform_full``).
    """
    u = jax.vmap(lambda v: jax.random.uniform(jax.random.fold_in(key, v)))(tid)
    return u * maxval if maxval != 1.0 else u


def global_uniform_full(key, n_real: int, tail: int):
    """The (n_real,) global-vertex-space uniform draw plus a zero tail for
    padding slots.  The draw shape must be exactly (n_real,) — threefry is
    not prefix-stable across shapes — so every consumer (``dcoarsen``'s
    clustering and the host clustering path's ``uniform(key, (n,))``) sees
    the same per-vertex stream.  This is the ONLY copy of the recipe;
    ``distributed.djet`` re-exports it.  Refinement no longer uses it —
    the engine's rebalance randomness is the shape-invariant
    :func:`tid_uniform` stream."""
    return jnp.concatenate(
        [jax.random.uniform(key, (n_real,)), jnp.zeros((tail,), jnp.float32)]
    )


def global_uniform_slice(key, gstart, *, n_local: int, n_real: int):
    """Owned-range slice of the global draw; the zero tail covers the last
    PE's padding slots (never accepted: masked by ``owned``)."""
    u = global_uniform_full(key, n_real, n_local)
    return jax.lax.dynamic_slice(u, (gstart,), (n_local,))


class SingleComm:
    """Single-device backend: the no-op rendering of the protocol."""

    kind = "single"

    def __init__(self, n_real: int):
        self.n_real = n_real

    def exchange(self, x):
        return x

    def lookup(self, ev: EdgeView, view, x_loc):
        return view[jnp.where(ev.live, ev.head, 0)]

    def psum(self, x):
        return x

    def gather(self, x):
        return x

    def uniform(self, key, ev: EdgeView):
        # ev.my_tid == global ids on the single path (padding slots read the
        # id-0 value; they are masked by ``owned`` / zero weight everywhere)
        return tid_uniform(key, jnp.where(ev.owned, ev.my_tid, 0))

    def apply_moves(self, ev: EdgeView, labels, tids, tgts, moved):
        idx = jnp.where(moved, tids, labels.shape[0])
        return labels.at[idx].set(tgts, mode="drop")


class AllGatherComm:
    """Baseline BSP backend: full-slice ``all_gather`` over mesh axis "pe".

    Must run inside a ``shard_map`` body.  ``gstart`` is the global id of
    this PE's first owned vertex (for the global-space uniform slice).
    """

    kind = "allgather"

    def __init__(self, gstart, n_local: int, n_real: int):
        self.gstart = gstart
        self.n_local = n_local
        self.n_real = n_real

    def exchange(self, x):
        return jax.lax.all_gather(x, "pe", tiled=True)

    def lookup(self, ev: EdgeView, view, x_loc):
        return view[jnp.where(ev.live, ev.head, 0)]

    def psum(self, x):
        return jax.lax.psum(x, "pe")

    def gather(self, x):
        return jax.lax.all_gather(x, "pe", tiled=True)

    def uniform(self, key, ev: EdgeView):
        # fold on TRUE global ids (gstart + slot), not the gathered-layout
        # my_tid (owner·n_local + offset): ranges are edge-balanced, so the
        # layout id is only order-isomorphic to — not equal to — the global
        # id, and it changes with P.  The owned prefix of each PE's range is
        # contiguous in global ids, so gstart + slot is exact.
        gid = self.gstart + jnp.arange(self.n_local, dtype=jnp.int32)
        return tid_uniform(key, jnp.where(ev.owned, gid, 0))

    def apply_moves(self, ev: EdgeView, labels, tids, tgts, moved):
        # tids are gathered-layout ids: owner·n_local + slot
        pe = jax.lax.axis_index("pe")
        slot = tids - pe * self.n_local
        ok = moved & (slot >= 0) & (slot < self.n_local)
        idx = jnp.where(ok, slot, self.n_local)
        return labels.at[idx].set(tgts, mode="drop")


class HaloComm:
    """Interface-only backend: ``exchange`` gathers only ``x[:h_local]``.

    Heads are halo codes (< P·h_local → remote interface slot, else local
    slot + P·h_local); tie-break ids are explicit global ids.  ``uniform``
    is the canonical per-gid :func:`tid_uniform` stream — O(n_local) per
    PE, which is exactly the scale property the halo variant exists for.
    The old ``"global"``/``"fold"`` mode split is gone: the fold stream
    became THE engine stream (the only one invariant under padding and
    batching — DESIGN.md §2), so both spellings of ``uniform_mode`` are
    still accepted and now identical.
    """

    kind = "halo"

    def __init__(self, P: int, h_local: int, n_local: int, n_real: int,
                 gstart, inv_perm, uniform_mode: str = "global",
                 kernel: str = "jnp", interpret: bool | None = None):
        assert uniform_mode in ("global", "fold"), uniform_mode
        assert kernel in ("jnp", "pallas"), kernel
        self.P = P
        self.h_local = h_local
        self.n_local = n_local
        self.n_real = n_real
        self.H = P * h_local
        self.gstart = gstart      # global id of this PE's first owned vertex
        self.inv_perm = inv_perm  # (n_local,) block-layout slot → halo slot
        self.uniform_mode = uniform_mode
        # move-application backend: "pallas" routes apply_moves through the
        # fused gid-compare kernel (repro.kernels.halo); the caller resolves
        # the envelope (kernels.halo.resolve_halo), this flag is final
        self.kernel = kernel
        self.interpret = interpret

    def exchange(self, x):
        return jax.lax.all_gather(x[: self.h_local], "pe", tiled=True)

    def lookup(self, ev: EdgeView, view, x_loc):
        code = ev.head
        remote = code < self.H
        r = view[jnp.where(remote, code, 0)]
        l = x_loc[jnp.where(remote, 0, code - self.H)]
        return jnp.where(remote, r, l)

    def psum(self, x):
        return jax.lax.psum(x, "pe")

    def gather(self, x):
        return jax.lax.all_gather(x, "pe", tiled=True)

    def uniform(self, key, ev: EdgeView):
        return tid_uniform(key, jnp.where(ev.owned, ev.my_tid, 0))

    def apply_moves(self, ev: EdgeView, labels, tids, tgts, moved):
        if self.kernel == "pallas":
            # fused VMEM pass (repro.kernels.halo): a dense gid-compare of
            # the whole move list against this PE's per-slot global ids —
            # bit-identical to the gather/scatter path below because
            # non-owned slots carry gid = PAD (match nothing) and the
            # engine's move list names each global id at most once
            # (tests/test_halo_kernel.py pins the equivalence)
            from repro.kernels.halo import apply_moves as _halo_apply

            return _halo_apply(labels, ev.my_tid, tids, tgts, moved,
                               interpret=self.interpret)
        # per-PE inverse-permutation gather, O(P·ncand): ownership of a
        # global move id is a range test against this PE's contiguous block,
        # its halo slot one gather through inv_perm.  (Replaces the old
        # (n_local × P·ncand) my_tid mask-compare.)  Ids past the owned
        # prefix of the block land on ~owned halo slots and are dropped.
        rel = tids - self.gstart
        inb = moved & (rel >= 0) & (rel < self.n_local)
        slot = self.inv_perm[jnp.where(inb, rel, 0)]
        ok = inb & ev.owned[slot]
        idx = jnp.where(ok, slot, self.n_local)
        return labels.at[idx].set(tgts, mode="drop")


def halo_edge_view(src, dst_code, head_gid, ew, nw, my_gid, owned) -> EdgeView:
    """EdgeView of one PE of a halo-sharded level — the single home of the
    halo coordinate convention (head = halo code, live = head_gid != PAD,
    tie-break ids = explicit global ids)."""
    from repro.core.graph import PAD  # deferred: breaks the core↔refine cycle

    return EdgeView(src=src, head=dst_code, live=head_gid != PAD, ew=ew,
                    head_tid=head_gid, my_tid=my_gid, nw=nw, owned=owned)


def edge_view_from_graph(g) -> EdgeView:
    """Single-device EdgeView of a :class:`repro.core.graph.Graph`."""
    from repro.core.graph import PAD  # deferred: breaks the core↔refine cycle

    live = g.col != PAD
    n = g.n
    ids = jnp.arange(n, dtype=jnp.int32)
    return EdgeView(
        src=g.src, head=g.col, live=live, ew=g.ew, head_tid=g.col,
        my_tid=ids, nw=g.nw, owned=jnp.ones((n,), bool),
    )
