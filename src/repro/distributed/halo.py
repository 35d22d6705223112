"""Interface-only halo exchange — the beyond-baseline optimisation of the
distributed Jet round (§Perf hillclimb #1, and exactly the paper's ghost
protocol: "interface vertices send g(v) to their ghost replicas").

The baseline BSP round all-gathers every PE's full label slice (n/P values
per PE).  But a remote PE only ever reads labels of *interface* vertices
(vertices with an edge crossing the PE boundary).  The halo layout therefore

  * permutes each PE's owned vertices interface-first; h_local = max
    interface count over PEs (static shape);
  * re-encodes every edge head as a *halo code*:
        code < P·h_local      → remote head: owner·h_local + slot in halo
        code ≥ P·h_local      → local head:  P·h_local + local slot
    (a head on another PE is by definition interface there, so its halo slot
    exists);
  * the per-round exchange becomes all_gather of labels[:h_local] — for
    meshy graphs h_local/n_local ≈ surface/volume → 10-30x fewer wire bytes.

Layout derivation is *sharded-native* (the tentpole of the on-device halo
V-cycle): the whole construction runs per PE on the already block-sharded
level (``dgraph.ShardedGraph``) —

  * the interface mask is ONE ghost-ownership compare over the block-layout
    edge list (a head's owner is its gathered-layout id // n_local);
  * the interface-first permutation is a per-PE stable device sort;
  * the halo slot map is one ``all_gather`` of the per-PE inverse
    permutations (n_local ints per PE — the same volume as one label
    ghost update).

Only ``h_local`` (one scalar, it sizes the static exchange shapes) crosses
to the host, alongside the 3 per-level scalars ``dcoarsen`` already
transfers; the level graph itself is never gathered.  Entry points:

  * :func:`halo_from_sharded`  — device path (``shard_map`` over mesh axis
    ``"pe"``), used by ``dcoarsen_hierarchy(halo=True)`` for every level of
    the sharded V-cycle;
  * :func:`shard_graph_halo`   — host path for a centralised
    :class:`~repro.core.graph.Graph`: block-shard via ``dgraph.shard_graph``
    (the single home of the vertex split), then run the *same* layout-pure
    core under ``vmap`` (the cross-PE gather degenerates to a reshape).

Vertex ids for the afterburner tie-break are carried explicitly
(``head_gid``/``my_gid``), so move decisions are bit-identical to the
baseline round (tested in tests/test_halo.py); the per-PE permutation and
its inverse ride along (``perm_loc``/``inv_perm``) so the greedy
rebalancer's move application is an O(P·ncand) inverse-permutation gather
(:meth:`repro.refine.comm.HaloComm.apply_moves`).

This module owns the halo *layout* (sharding, label conversion, halo
codes); the refinement arithmetic lives once in the unified engine
(``repro.refine.engine``), adapted here via
:class:`~repro.refine.comm.HaloComm`.  The fused whole-level halo program
is ``repro.refine.drivers.make_refine_level_halo``.
"""

from __future__ import annotations

import dataclasses
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import telemetry
from repro.core.graph import PAD, Graph
from repro.distributed.dgraph import ShardedGraph, owned_mask, shard_graph
from repro.sharding.compat import shard_map


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class HaloShardedGraph:
    src: jax.Array       # (P, m_local) local (permuted) row ids
    dst_code: jax.Array  # (P, m_local) halo codes (see module docstring)
    head_gid: jax.Array  # (P, m_local) global id of head (tie-breaks), PAD pad
    ew: jax.Array        # (P, m_local)
    nw: jax.Array        # (P, n_local)
    my_gid: jax.Array    # (P, n_local) global id of each owned slot
    owned: jax.Array     # (P, n_local) bool
    perm_loc: jax.Array  # (P, n_local) halo slot → block-layout slot
    inv_perm: jax.Array  # (P, n_local) block-layout slot → halo slot
    gstart: jax.Array    # (P,) global id of each PE's first owned vertex
    n_real: int = dataclasses.field(metadata=dict(static=True))
    P: int = dataclasses.field(metadata=dict(static=True))
    n_local: int = dataclasses.field(metadata=dict(static=True))
    m_local: int = dataclasses.field(metadata=dict(static=True))
    h_local: int = dataclasses.field(metadata=dict(static=True))


# --------------------------------------------------------------------------
# layout-pure per-PE core (no collectives; shared by the shard_map and the
# host/vmap drivers, so both entry points produce bit-identical layouts)
# --------------------------------------------------------------------------

def _interface_local(src, dst, owned, pe, *, n_local: int):
    """Interface mask over one PE's owned slots: one ghost-ownership compare
    over the block-layout edge list.  Heads are gathered-layout ids, so a
    head's owner is ``dst // n_local``; marking *tails* of remote edges is
    exhaustive because every undirected edge is stored as two directed
    copies — a vertex with a remote neighbour always has a local copy."""
    live = dst != PAD
    owner = jnp.where(live, dst // n_local, pe)
    remote = live & (owner != pe)
    hit = jnp.zeros((n_local,), jnp.int32).at[src].max(remote.astype(jnp.int32))
    return (hit > 0) & owned


def _interface_perm_local(iface, owned, *, n_local: int):
    """Interface-first permutation of one PE's slots.

    Returns (perm_loc, inv, n_if): the halo→block slot map, its inverse and
    the interface count.  The stable counting sort on class (interface,
    interior, padding) keeps ascending slot — i.e. ascending global id —
    order inside each class, so the layout matches the host-side
    construction exactly; it is three cumulative counts, not a device
    sort."""
    cls = jnp.where(iface, 0, jnp.where(owned, 1, 2)).astype(jnp.int32)
    inv = jnp.zeros((n_local,), jnp.int32)
    start = jnp.int32(0)
    for c in range(3):
        in_c = (cls == c).astype(jnp.int32)
        inv = jnp.where(cls == c, start + jnp.cumsum(in_c) - 1, inv)
        start = start + jnp.sum(in_c)
    perm_loc = jnp.zeros((n_local,), jnp.int32).at[inv].set(
        jnp.arange(n_local, dtype=jnp.int32))
    return perm_loc, inv, jnp.sum(iface.astype(jnp.int32))


def _halo_encode_local(src, dst, nw, owned, vtx_start, pe, perm_loc, inv,
                       inv_full, *, P_: int, n_local: int, h_local: int):
    """Re-encode one PE's block-layout slice into the halo layout.

    Pure per-PE arithmetic; the only cross-PE input is ``inv_full``, the
    concatenated (P·n_local,) inverse permutations, indexed directly by the
    gathered-layout head id.  A remote head is interface at its owner, so
    its halo slot (``inv_full[dst] < h_local``) always exists."""
    H = P_ * h_local
    live = dst != PAD
    d = jnp.where(live, dst, 0)
    owner = d // n_local
    new_slot = inv_full[d]
    code = jnp.where(owner == pe, H + new_slot, owner * h_local + new_slot)
    dst_code = jnp.where(live, code, H).astype(jnp.int32)
    head_gid = jnp.where(live, vtx_start[owner] + d % n_local,
                         PAD).astype(jnp.int32)
    src_h = inv[src]
    owned_h = owned[perm_loc]
    nw_h = nw[perm_loc]
    my_gid = jnp.where(owned_h, vtx_start[pe] + perm_loc, PAD).astype(jnp.int32)
    return src_h, dst_code, head_gid, my_gid, nw_h, owned_h


# --------------------------------------------------------------------------
# device driver: derive the halo layout from a sharded level under shard_map
# --------------------------------------------------------------------------

_SH = P("pe", None)


@lru_cache(maxsize=128)
def _iface_count_fn(mesh, P_: int, n_local: int):
    def per_pe(src, dst, owned):
        pe = jax.lax.axis_index("pe")
        iface = _interface_local(src[0], dst[0], owned[0], pe,
                                 n_local=n_local)
        return jax.lax.pmax(jnp.sum(iface.astype(jnp.int32)), "pe")

    return jax.jit(shard_map(per_pe, mesh=mesh, in_specs=(_SH, _SH, _SH),
                             out_specs=P()))


@lru_cache(maxsize=128)
def _halo_build_fn(mesh, P_: int, n_local: int, m_local: int, h_local: int):
    def per_pe(src, dst, nw, owned, vtx_start):
        pe = jax.lax.axis_index("pe")
        iface = _interface_local(src[0], dst[0], owned[0], pe,
                                 n_local=n_local)
        perm_loc, inv, _ = _interface_perm_local(iface, owned[0],
                                                 n_local=n_local)
        inv_full = jax.lax.all_gather(inv, "pe", tiled=True)
        src_h, dst_code, head_gid, my_gid, nw_h, owned_h = _halo_encode_local(
            src[0], dst[0], nw[0], owned[0], vtx_start, pe, perm_loc, inv,
            inv_full, P_=P_, n_local=n_local, h_local=h_local)
        return tuple(x[None] for x in (src_h, dst_code, head_gid, my_gid,
                                       nw_h, owned_h, perm_loc, inv))

    return jax.jit(shard_map(
        per_pe, mesh=mesh,
        in_specs=(_SH, _SH, _SH, _SH, P()),
        out_specs=(_SH,) * 8,
    ))


def halo_from_sharded(mesh, sg: ShardedGraph) -> HaloShardedGraph:
    """Derive the halo layout of an already-sharded level ON DEVICE.

    The interface mask, interface-first permutation, halo slot map and
    re-encoded edge heads are all computed per PE under ``shard_map``; the
    only host transfer is the ``h_local`` scalar (it sizes the static
    exchange shapes).  The level graph is never gathered."""
    owned = owned_mask(sg)
    telemetry.count_dispatch("halo_count")
    h_local = max(1, telemetry.host_read(
        "h_local", _iface_count_fn(mesh, sg.P, sg.n_local)(
            sg.src, sg.dst, owned)))
    telemetry.count_dispatch("halo_build")
    src_h, dst_code, head_gid, my_gid, nw_h, owned_h, perm_loc, inv = (
        _halo_build_fn(mesh, sg.P, sg.n_local, sg.m_local, h_local)(
            sg.src, sg.dst, sg.nw, owned, sg.vtx_start))
    return HaloShardedGraph(
        src=src_h, dst_code=dst_code, head_gid=head_gid, ew=sg.ew, nw=nw_h,
        my_gid=my_gid, owned=owned_h, perm_loc=perm_loc, inv_perm=inv,
        gstart=sg.vtx_start, n_real=sg.n_real, P=sg.P, n_local=sg.n_local,
        m_local=sg.m_local, h_local=h_local,
    )


# --------------------------------------------------------------------------
# host driver: the same core under vmap (setup-time, mesh-free)
# --------------------------------------------------------------------------

def _halo_from_sharded_host(sg: ShardedGraph) -> HaloShardedGraph:
    """Mesh-free rendering of the same layout-pure core: ``vmap`` over the
    PE axis, the cross-PE gather of inverse permutations is a reshape."""
    owned = owned_mask(sg)
    pes = jnp.arange(sg.P, dtype=jnp.int32)
    iface = jax.vmap(partial(_interface_local, n_local=sg.n_local))(
        sg.src, sg.dst, owned, pes)
    perm_loc, inv, n_if = jax.vmap(
        partial(_interface_perm_local, n_local=sg.n_local))(iface, owned)
    h_local = max(1, telemetry.host_read("h_local", jnp.max(n_if)))
    src_h, dst_code, head_gid, my_gid, nw_h, owned_h = jax.vmap(
        partial(_halo_encode_local, P_=sg.P, n_local=sg.n_local,
                h_local=h_local),
        in_axes=(0, 0, 0, 0, None, 0, 0, 0, None),
    )(sg.src, sg.dst, sg.nw, owned, sg.vtx_start, pes, perm_loc, inv,
      inv.reshape(-1))
    return HaloShardedGraph(
        src=src_h, dst_code=dst_code, head_gid=head_gid, ew=sg.ew, nw=nw_h,
        my_gid=my_gid, owned=owned_h, perm_loc=perm_loc, inv_perm=inv,
        gstart=sg.vtx_start, n_real=sg.n_real, P=sg.P, n_local=sg.n_local,
        m_local=sg.m_local, h_local=h_local,
    )


def shard_graph_halo(g: Graph, P: int) -> tuple[HaloShardedGraph, np.ndarray]:
    """Halo-shard a centralised :class:`Graph`: block split via
    ``dgraph.shard_graph`` (the single home of the vertex split used by both
    refinement layouts), then the shared layout core.  Returns
    (sharded, perm) where ``perm`` maps (pe, halo slot) → original vertex id
    ((P, n_local), -1 = pad) for host-side label conversion."""
    hsg = _halo_from_sharded_host(shard_graph(g, P))
    perm = np.where(np.asarray(hsg.owned),
                    np.asarray(hsg.my_gid).astype(np.int64), -1)
    return hsg, perm


# --------------------------------------------------------------------------
# label layout conversions
# --------------------------------------------------------------------------

def halo_labels_to_sharded(sg: HaloShardedGraph, perm: np.ndarray, labels):
    """(n,) global labels → halo layout (host-side, via the perm table)."""
    lab = np.asarray(labels)
    out = np.zeros((sg.P, sg.n_local), np.int32)
    ok = perm >= 0
    out[ok] = lab[perm[ok]]
    return jnp.asarray(out)


def halo_labels_from_sharded(sg: HaloShardedGraph, perm: np.ndarray, lab_sh):
    """Halo layout → (n,) global labels (host-side, via the perm table)."""
    lab = np.asarray(lab_sh)
    out = np.zeros(sg.n_real, np.int32)
    ok = perm >= 0
    out[perm[ok]] = lab[ok]
    return jnp.asarray(out)


def block_labels_to_halo(hsg: HaloShardedGraph, lab_sh):
    """(P, n_local) block-layout labels → halo (interface-first) layout.

    A per-PE gather through ``perm_loc`` — device-resident, this is how
    ``duncoarsen`` output flows straight into the halo level program.
    The sharded V-cycle no longer calls this between dispatches: the
    conversion is fused *into* the level program
    (``drivers.make_refine_level_halo(relayout=True)``); this standalone
    entry serves the host paths, benchmarks and tests."""
    return jnp.take_along_axis(lab_sh, hsg.perm_loc, axis=1)


def block_labels_from_halo(hsg: HaloShardedGraph, lab_h):
    """Halo layout → (P, n_local) block layout: the scatter through
    ``perm_loc`` (equivalently the gather through ``inv_perm`` — the
    permutation is total)."""
    rows = jnp.arange(hsg.P, dtype=jnp.int32)[:, None]
    return jnp.zeros_like(lab_h).at[rows, hsg.perm_loc].set(lab_h)


# --------------------------------------------------------------------------
# per-PE adapters over the unified engine (shard_map bodies)
# --------------------------------------------------------------------------

def _halo_backends(sg: HaloShardedGraph, *, k: int, uniform_mode: str):
    """EdgeView + comm/gain backends for one PE of a halo-sharded level.

    ``sg`` arrays still carry the leading PE axis; per-PE slices are taken
    here so callers can pass the pytree straight through ``shard_map``.
    """
    from repro.refine.comm import HaloComm, halo_edge_view
    from repro.refine.gain import make_gain

    ev = halo_edge_view(sg.src[0], sg.dst_code[0], sg.head_gid[0], sg.ew[0],
                        sg.nw[0], sg.my_gid[0], sg.owned[0])
    cm = HaloComm(sg.P, sg.h_local, sg.n_local, sg.n_real,
                  gstart=sg.gstart[0], inv_perm=sg.inv_perm[0],
                  uniform_mode=uniform_mode)
    return ev, cm, make_gain("jnp", ev, k)


def halo_jet_round_local(sg: HaloShardedGraph, labels_loc, locked, tau,
                         *, k: int):
    from repro.refine import engine

    ev, cm, gb = _halo_backends(sg, k=k, uniform_mode="global")
    return engine.jet_move(cm, gb, ev, labels_loc, locked, tau, k)


def halo_prob_pass_local(sg: HaloShardedGraph, labels_loc, key, lmax,
                         *, k: int, uniform_mode: str = "fold"):
    """Alg. 1 pass under the halo protocol.  Defaults to the O(n_local)
    fold-in-per-gid uniform stream (the scale setting used by the launch
    dry-run); the fused level driver (``repro.refine.drivers``) uses the
    global-vertex-space stream for the cross-backend determinism contract.
    """
    from repro.refine import engine

    ev, cm, gb = _halo_backends(sg, k=k, uniform_mode=uniform_mode)
    return engine.prob_pass(cm, gb, ev, labels_loc, key, lmax, k)


def make_halo_jet_round(mesh, sg: HaloShardedGraph, k: int):
    def per_pe(sg_, labels, locked, tau):
        new, move = halo_jet_round_local(sg_, labels[0], locked[0], tau, k=k)
        return new[None], move[None]

    sg_specs = HaloShardedGraph(
        src=_SH, dst_code=_SH, head_gid=_SH, ew=_SH, nw=_SH, my_gid=_SH,
        owned=_SH, perm_loc=_SH, inv_perm=_SH, gstart=P("pe"),
        n_real=sg.n_real, P=sg.P, n_local=sg.n_local, m_local=sg.m_local,
        h_local=sg.h_local,
    )
    return jax.jit(shard_map(
        per_pe, mesh=mesh,
        in_specs=(sg_specs, _SH, _SH, P()),
        out_specs=(_SH, _SH),
    ))
