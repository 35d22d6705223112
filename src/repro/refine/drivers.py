"""Compiled entry points of the unified refinement engine.

One refinement *level* — all temperature rounds, all inner (Jet →
rebalance → patience) iterations, all greedy/probabilistic rebalance
epochs — executes as a SINGLE compiled program per backend combination:

  * :func:`refine_single`            — single device, no mesh;
  * :func:`make_refine_level_sharded` — baseline BSP protocol under
    ``shard_map`` (``dgraph.ShardedGraph`` layout);
  * :func:`make_refine_level_halo`    — interface-only halo protocol
    (``halo.HaloShardedGraph`` layout);
  * :func:`make_lp_level_sharded`     — the fused dLP baseline level.

Every factory takes ``variant=`` — a registered move-generation rule from
``refine/variants.py`` (the name is part of the static cache key); lp-mode
variants swap the level program for ``engine.lp_level`` under the same
comm backend.

The module keeps two counters for the no-per-round-dispatch contract:
``DISPATCH_COUNT`` increments once per level-refinement *call* and
``TRACE_COUNT`` once per *trace* — a V-cycle over L levels must show
exactly L dispatches (asserted in tests and reported by the scaling
benchmark), where the pre-refactor drivers issued O(rounds · inner)
dispatches per level.  Each dispatch also goes to the per-call recorders
of ``repro.telemetry`` (as ``refine_<kind>``), which give a
``dpartition`` call its own counts.

Factories are memoised on their static configuration, so repeated V-cycles
over same-shaped levels reuse compiled programs.
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import telemetry
from repro.refine import engine
from repro.refine.schedule import ToleranceSchedule, resolve_schedule
from repro.refine.comm import (
    AllGatherComm,
    EdgeView,
    HaloComm,
    SingleComm,
    edge_view_from_graph,
    halo_edge_view,
)
from repro.refine.gain import EDGE_LAYOUT, make_gain, resolve_gain
from repro.refine.variants import resolve_variant
from repro.sharding.compat import shard_map

DISPATCH_COUNT = 0   # level-refinement calls (python → device dispatches)
TRACE_COUNT = 0      # traces of level programs (≤ DISPATCH_COUNT)
DISPATCHES: dict[str, int] = {}   # per comm-backend kind
TRACES: dict[str, int] = {}


def reset_counters() -> None:
    global DISPATCH_COUNT, TRACE_COUNT
    DISPATCH_COUNT = 0
    TRACE_COUNT = 0
    DISPATCHES.clear()
    TRACES.clear()


# Cross-call buffer donation (serving path): donating the labels carry lets
# XLA reuse the flush's label buffer for the output instead of allocating a
# fresh one per rung.  Donation never changes values — the program reads the
# input before the runtime recycles it (DESIGN.md §2).
def _donation_supported() -> bool:
    """XLA implements input-buffer donation on gpu/tpu only; the cpu backend
    ignores it with a per-call warning, so the serving path degrades to the
    undonated program there instead of spamming logs."""
    return jax.default_backend() in ("gpu", "tpu")


# test hook: force donate_argnums through on cpu (jax still runs the program,
# it just cannot actually reuse the buffer) so the donated rendering's
# bit-identity is pinned without TPU hardware
FORCE_DONATE = False


def _count_dispatch(kind: str) -> None:
    global DISPATCH_COUNT
    DISPATCH_COUNT += 1
    DISPATCHES[kind] = DISPATCHES.get(kind, 0) + 1
    telemetry.count_dispatch(f"refine_{kind}")


def _count_trace(kind: str) -> None:
    global TRACE_COUNT
    TRACE_COUNT += 1
    TRACES[kind] = TRACES.get(kind, 0) + 1


# --------------------------------------------------------------------------
# per-level tolerance resolution (refine/schedule.py)
# --------------------------------------------------------------------------

def level_tolerances(schedule: str | ToleranceSchedule, eps: float,
                     n_levels: int, k: int,
                     eps_coarse: float | None = None,
                     w_fracs=None) -> tuple[float, ...]:
    """Resolve one V-cycle's per-level imbalance tolerances (index 0 =
    coarsest … ``n_levels − 1`` = finest).

    Each fused level program then receives its own static ``(taus, eps_l)``
    pair: the τ vector stays the variant's temperature schedule, and the
    level's ``L_max`` is computed from ``eps_l`` instead of the single
    global tolerance.  ``eps_l`` is a host-side float feeding an
    already-traced scalar argument, so a non-constant schedule adds no host
    round-trips and no retraces.  ``w_fracs`` is the coarsest-first
    sequence of per-level ``w_max/c(V)`` fractions the ``adaptive`` mode
    consumes (``schedule.weight_frac``); other modes ignore it."""
    return resolve_schedule(schedule, eps_coarse).eps_levels(
        eps, n_levels, k, w_fracs)


# --------------------------------------------------------------------------
# max-degree probes (static setup scalars that size the padded adjacency)
# --------------------------------------------------------------------------

def graph_max_deg(g) -> int:
    return max(telemetry.host_read(
        "max_deg", g.degrees,
        lambda d: int(np.asarray(d).max(initial=0))), 1)


@partial(jax.jit, static_argnames=("n_local",))
def _sharded_degrees(src, dst, n_local: int):
    from repro.core.graph import PAD  # deferred: breaks the core↔refine cycle

    live = (dst != PAD).astype(jnp.float32)
    deg = jax.vmap(
        lambda s, l: jax.ops.segment_sum(l, s, num_segments=n_local)
    )(src, live)
    return jnp.max(deg)


def sharded_max_deg(src, dst, n_local: int) -> int:
    """True max degree of a sharded level — one scalar crosses to the host
    at setup time (it picks the static padded-adjacency width)."""
    telemetry.count_dispatch("max_deg")
    return max(telemetry.host_read(
        "max_deg", _sharded_degrees(src, dst, n_local)), 1)


def _need_max_deg(gain: str) -> bool:
    return gain in ("pallas", "auto")


def _level_backends(gain_kind: str, halo_kind: str | None,
                    variant: str) -> dict:
    """A level program's ``run.backends``: the gain and halo backends the
    fallback rules resolved, and the edge layout its afterburner runs in
    (``EDGE_LAYOUT``; None for lp-mode variants, which run none)."""
    jet = resolve_variant(variant).mode == "jet"
    return {"gain": gain_kind, "halo": halo_kind,
            "afterburner": EDGE_LAYOUT[gain_kind] if jet else None}


# --------------------------------------------------------------------------
# single device
# --------------------------------------------------------------------------

@partial(jax.jit, static_argnames=(
    "k", "patience", "max_inner", "gain_kind", "max_deg", "interpret",
    "variant"))
def _refine_single_jit(g, labels, key, lmax, taus, *, k, patience, max_inner,
                       gain_kind, max_deg, interpret, variant):
    _count_trace("single")
    ev = edge_view_from_graph(g)
    cm = SingleComm(g.n)
    gb = make_gain(gain_kind, ev, k, max_deg, interpret)
    var = resolve_variant(variant)
    if var.mode == "lp":
        return engine.lp_level(cm, gb, ev, labels, key, lmax, k)
    return engine.refine_level(cm, gb, ev, labels, key, lmax, taus, k,
                               patience, max_inner, move_fn=var.move)


def refine_single(g, labels, k, key, lmax, taus, *, patience=12, max_inner=64,
                  gain="jnp", interpret=None, variant="jet"):
    """Fused single-device level refinement (one dispatch).  ``variant``
    names a registered move-generation rule (``refine/variants.py``);
    lp-mode variants ignore ``taus``/``patience``/``max_inner``."""
    resolve_variant(variant)  # fail on a typo before compiling anything
    max_deg = graph_max_deg(g) if _need_max_deg(gain) else None
    gain_kind = resolve_gain(gain, k, max_deg)
    _count_dispatch("single")
    return _refine_single_jit(
        g, labels, key, lmax, jnp.asarray(taus, jnp.float32),
        k=k, patience=patience, max_inner=max_inner, gain_kind=gain_kind,
        max_deg=max_deg if gain_kind == "pallas" else None,
        interpret=interpret, variant=variant)


# --------------------------------------------------------------------------
# block-sharded (baseline all-gather BSP) levels
# --------------------------------------------------------------------------

def _sharded_edge_view(src, dst, ew, nw, owned, n_local: int) -> EdgeView:
    from repro.core.graph import PAD  # deferred: breaks the core↔refine cycle

    pe = jax.lax.axis_index("pe")
    my_tid = pe * n_local + jnp.arange(n_local, dtype=jnp.int32)
    return EdgeView(src=src, head=dst, live=dst != PAD, ew=ew, head_tid=dst,
                    my_tid=my_tid, nw=nw, owned=owned)


@lru_cache(maxsize=128)
def _sharded_level_fn(mesh, k, n_local, n_real, patience, max_inner,
                      gain_kind, max_deg, interpret, variant):
    var = resolve_variant(variant)
    kind = "lp" if var.mode == "lp" else "sharded"

    def refine_allgather(src, dst, ew, nw, owned, gstart, labels, key, lmax, taus):
        _count_trace(kind)
        ev = _sharded_edge_view(src[0], dst[0], ew[0], nw[0], owned[0],
                                n_local)
        cm = AllGatherComm(gstart[0], n_local, n_real)
        gb = make_gain(gain_kind, ev, k, max_deg, interpret)
        if var.mode == "lp":
            out = engine.lp_level(cm, gb, ev, labels[0], key, lmax, k)
        else:
            out = engine.refine_level(cm, gb, ev, labels[0], key, lmax, taus,
                                      k, patience, max_inner,
                                      move_fn=var.move)
        return out[None]

    sh = P("pe", None)
    return kind, jax.jit(shard_map(
        refine_allgather, mesh=mesh,
        in_specs=(sh, sh, sh, sh, sh, P("pe"), sh, P(), P(), P()),
        out_specs=sh,
    ))


def make_refine_level_sharded(mesh, sg, k, *, rounds_taus, patience=12,
                              max_inner=64, gain="jnp", interpret=None,
                              variant="jet"):
    """Fused level refinement over a :class:`ShardedGraph`.

    Returns ``run(lab_sh, key, lmax) -> lab_sh`` — one dispatch per call.
    ``rounds_taus`` is the temperature vector; ``variant`` names the
    registered move-generation rule (lp-mode variants ignore the taus).
    ``run.backends`` names the backends the fallback rules resolved
    (:func:`_level_backends`; ``"halo": None`` — this protocol has no halo
    kernel).
    """
    from repro.distributed.dgraph import owned_mask

    resolve_variant(variant)
    max_deg = (sharded_max_deg(sg.src, sg.dst, sg.n_local)
               if _need_max_deg(gain) else None)
    gain_kind = resolve_gain(gain, k, max_deg)
    kind, fn = _sharded_level_fn(
        mesh, k, sg.n_local, sg.n_real, patience, max_inner, gain_kind,
        max_deg if gain_kind == "pallas" else None, interpret, variant)
    owned = owned_mask(sg)
    taus = jnp.asarray(rounds_taus, jnp.float32)

    def run(lab_sh, key, lmax):
        _count_dispatch(kind)
        return fn(sg.src, sg.dst, sg.ew, sg.nw, owned, sg.vtx_start, lab_sh,
                  key, jnp.float32(lmax), taus)

    run.backends = _level_backends(gain_kind, None, variant)
    return run


def make_lp_level_sharded(mesh, sg, k, *, gain="jnp", interpret=None):
    return make_refine_level_sharded(
        mesh, sg, k, rounds_taus=[0.0], gain=gain, interpret=interpret,
        variant="lp")


# --------------------------------------------------------------------------
# request-batched (pad-to-bucket + vmap) levels — DESIGN.md §2
# --------------------------------------------------------------------------

def _batched_edge_view(col, src, ew, nw, n_real, n_bucket: int) -> EdgeView:
    """Per-slot EdgeView of one bucket slot: the single-device view with
    ``owned`` restricted to the real prefix (padding slots carry nw = 0 /
    PAD heads and are inert in every engine reduction — the masking
    contract of ``repro.graphs.batch``)."""
    from repro.core.graph import PAD  # deferred: breaks the core↔refine cycle

    ids = jnp.arange(n_bucket, dtype=jnp.int32)
    return EdgeView(src=src, head=col, live=col != PAD, ew=ew, head_tid=col,
                    my_tid=ids, nw=nw, owned=ids < n_real)


# maxsize sizing (serving bucket mix): geometric n-buckets give ~14 distinct
# n (2^3 … 2^17) with at most 2-3 m-buckets each; times ~6 registered
# variants and 2 gain backends a realistic mixed request stream touches a few
# hundred distinct keys.  At the old 128 such a mix cycled the cache and every
# flush retraced (silent thrash); 512 keeps the whole realistic mix resident
# while still bounding memory (each entry is one traced program).
@lru_cache(maxsize=512)
def _batched_level_fn(b, n_bucket, m_bucket, k, patience, max_inner,
                      gain_kind, max_deg, interpret, variant, donate):
    """One compiled program refining B bucket slots at once: ``jax.vmap``
    of the single-device level program over the batch axis.  Memoised on
    the full bucket key ``(B, n_bucket, m_bucket, k, variant, taus-shape
    statics, gain backend, …)`` so every batch landing in the same bucket
    reuses the compiled dispatch.  ``donate=True`` donates the labels carry
    (``donate_argnums``) so XLA recycles the flush's label buffer for the
    output — the serving scheduler's steady-state setting; values are
    identical either way (tests/test_serve.py pins it)."""
    var = resolve_variant(variant)

    def per_slot(col, src, ew, nw, n_real, labels, key, lmax, taus):
        ev = _batched_edge_view(col, src, ew, nw, n_real, n_bucket)
        cm = SingleComm(n_bucket)
        gb = make_gain(gain_kind, ev, k, max_deg, interpret)
        if var.mode == "lp":
            return engine.lp_level(cm, gb, ev, labels, key, lmax, k)
        return engine.refine_level(cm, gb, ev, labels, key, lmax, taus, k,
                                   patience, max_inner, move_fn=var.move)

    def fn(col, src, ew, nw, n_real, labels, keys, lmaxs, taus):
        _count_trace("batched")
        return jax.vmap(per_slot, in_axes=(0,) * 8 + (None,))(
            col, src, ew, nw, n_real, labels, keys, lmaxs, taus)

    # labels is positional arg 5 of fn — the only carry the caller never
    # reuses after the dispatch, hence the only donation candidate
    return jax.jit(fn, donate_argnums=(5,) if donate else ())


def batched_max_deg(bg) -> int:
    """Static padded-adjacency width of a batch: the max degree over every
    slot, rounded up to the Pallas kernel's degree-chunk multiple so nearby
    batches share one cache entry (wider padding columns carry weight 0 —
    exact zero adds, bit-identical gains)."""
    deg = np.asarray(bg.row_ptr[:, 1:] - bg.row_ptr[:, :-1])
    d = max(int(deg.max(initial=0)), 1)
    return -(-d // 16) * 16


def make_refine_level_batched(bg, k, *, rounds_taus, patience=12,
                              max_inner=64, gain="jnp", interpret=None,
                              variant="jet", donate=False):
    """Fused level refinement over a :class:`repro.graphs.batch.BatchedGraph`.

    Returns ``run(labels, keys, lmaxs) -> labels`` with ``labels`` (B, n),
    ``keys`` (B,)-stacked PRNG keys and ``lmaxs`` (B,) per-slot balance
    bounds — ONE dispatch refines all B slots.  Bit-identical per slot to
    :func:`refine_single` on the unpadded graph (tests/test_batch_parity.py).

    ``donate=True`` requests label-buffer donation (the serving scheduler's
    steady-state zero-allocation setting); it is honoured only where XLA
    implements donation (gpu/tpu — see :func:`_donation_supported`), so on
    cpu the flag resolves to the same cached program as ``donate=False``
    instead of warning per call.
    """
    resolve_variant(variant)
    max_deg = batched_max_deg(bg) if _need_max_deg(gain) else None
    gain_kind = resolve_gain(gain, k, max_deg)
    donate = bool(donate) and (_donation_supported() or FORCE_DONATE)
    fn = _batched_level_fn(
        bg.b, bg.n, bg.m, k, patience, max_inner, gain_kind,
        max_deg if gain_kind == "pallas" else None, interpret, variant,
        donate)
    taus = jnp.asarray(rounds_taus, jnp.float32)

    def run(labels, keys, lmaxs):
        _count_dispatch("batched")
        return fn(bg.col, bg.src, bg.ew, bg.nw, bg.n_real, labels, keys,
                  jnp.asarray(lmaxs, jnp.float32), taus)

    return run


# keyed on coarsest-level buckets only (coarsen_until clamps n), so far
# fewer distinct keys than the level factory — 128 is ample headroom
@lru_cache(maxsize=128)
def _batched_init_fn(b, n_bucket, m_bucket, k, n_restarts):
    """One compiled program running the full multi-restart initial
    partitioning for B coarsest graphs: per slot, the exact restart chain
    of ``repro.core.initial.initial_partition`` (greedy seed → 2-round Jet
    refine per restart, identical key splits) unrolled inside the trace.
    Returns stacked (B, R, n) labels plus (B, R) cuts / overloads; the
    winner selection stays on the host (it is a float compare chain, bit-
    identical to the solo path's)."""
    from repro.core.initial import greedy_seed_arith
    from repro.core.refine import temperature_schedule

    var = resolve_variant("jet")
    taus = jnp.asarray(temperature_schedule(2), jnp.float32)

    def per_slot(col, src, ew, nw, n_real, key, lmax):
        ev = _batched_edge_view(col, src, ew, nw, n_real, n_bucket)
        cm = SingleComm(n_bucket)
        gb = make_gain("jnp", ev, k, None, None)
        labs, cuts, ovs = [], [], []
        for _ in range(n_restarts):
            key, k1, k2 = jax.random.split(key, 3)
            labels = greedy_seed_arith(nw, k, k1)
            labels = engine.refine_level(cm, gb, ev, labels, k2, lmax, taus,
                                         k, 6, 24, move_fn=var.move)
            labs.append(labels)
            cuts.append(engine.cut_of(cm, ev, labels))
            ovs.append(engine.overload_of(cm, ev, labels, k, lmax))
        return (jnp.stack(labs), jnp.stack(cuts), jnp.stack(ovs))

    @jax.jit
    def fn(col, src, ew, nw, n_real, keys, lmaxs):
        _count_trace("batched_init")
        return jax.vmap(per_slot)(col, src, ew, nw, n_real, keys, lmaxs)

    return fn


def initial_partition_batched(bg, k, keys, lmaxs, n_restarts: int = 4,
                              as_numpy: bool = True):
    """Multi-restart initial partitioning of B coarsest graphs in ONE
    dispatch (B × ``n_restarts`` restart slots in one vmapped program).

    Returns host arrays ``(labels (B, R, n), cuts (B, R), overloads
    (B, R))``; the caller replays the solo path's winner rule per slot.
    ``as_numpy=False`` returns the device arrays instead — the multi-bucket
    serving runner enqueues every bucket's init dispatch before blocking on
    any of them (the host conversion is where the sync happens).
    """
    fn = _batched_init_fn(bg.b, bg.n, bg.m, k, n_restarts)
    _count_dispatch("batched_init")
    labs, cuts, ovs = fn(bg.col, bg.src, bg.ew, bg.nw, bg.n_real, keys,
                         jnp.asarray(lmaxs, jnp.float32))
    if not as_numpy:
        return labs, cuts, ovs
    return np.asarray(labs), np.asarray(cuts), np.asarray(ovs)


def _lru_stats(cached_fn) -> dict:
    """{hits, misses, evictions, currsize, maxsize} of one lru_cache'd
    factory.  Every miss inserts exactly one entry and entries only leave by
    LRU eviction, so ``evictions = misses − currsize`` (exact as long as
    ``cache_clear`` is never called, which nothing in the repo does)."""
    info = cached_fn.cache_info()
    return {"hits": info.hits, "misses": info.misses,
            "evictions": max(0, info.misses - info.currsize),
            "currsize": info.currsize, "maxsize": info.maxsize}


def cache_stats() -> dict:
    """Per-factory retrace-cache statistics (hits/misses/evictions) of every
    memoised level-program factory — the serving scheduler logs the
    ``level``/``init`` entries per flush, and ``bench.py`` records them per
    batched cell.  A nonzero ``evictions`` under a realistic bucket mix
    means the factory maxsize is too small (the cache is thrashing and every
    flush retraces)."""
    return {"level": _lru_stats(_batched_level_fn),
            "init": _lru_stats(_batched_init_fn),
            "sharded": _lru_stats(_sharded_level_fn),
            "halo": _lru_stats(_halo_level_fn)}


# --------------------------------------------------------------------------
# halo (interface-only) levels
# --------------------------------------------------------------------------

@lru_cache(maxsize=128)
def _halo_level_fn(mesh, k, n_local, n_real, n_pe, h_local, patience,
                   max_inner, gain_kind, max_deg, interpret, uniform_mode,
                   variant, halo_kind, relayout):
    """``halo_kind`` selects the move-application backend of
    :class:`HaloComm` (the fused Pallas gid-compare kernel vs the XLA
    gather/scatter path — same switch as the gain backend, resolved by the
    caller).  ``relayout=True`` fuses the halo↔block label relayout into
    the level program: the program takes *block-layout* labels, permutes
    them to the interface-first layout in-trace (a gather through
    ``perm_loc``), refines, and permutes back through ``inv_perm`` — the
    layout conversions compile into the one level dispatch instead of
    standing alone as separate ``take_along_axis`` dispatches.  Both
    directions are XLA gathers on every backend (the old scatter rendering
    of ``block_labels_from_halo`` is the same map since the permutations
    are total); Mosaic cannot express a whole-shard gather (DESIGN.md §5)."""
    var = resolve_variant(variant)

    def refine_halo(src, dst_code, head_gid, ew, nw, my_gid, owned, inv_perm,
                    perm_loc, gstart, labels, key, lmax, taus):
        _count_trace("halo")
        ev = halo_edge_view(src[0], dst_code[0], head_gid[0], ew[0], nw[0],
                            my_gid[0], owned[0])
        cm = HaloComm(n_pe, h_local, n_local, n_real, gstart=gstart[0],
                      inv_perm=inv_perm[0], uniform_mode=uniform_mode,
                      kernel=halo_kind, interpret=interpret)
        gb = make_gain(gain_kind, ev, k, max_deg, interpret)
        lab = labels[0]
        if relayout:
            lab = lab[perm_loc[0]]
        if var.mode == "lp":
            out = engine.lp_level(cm, gb, ev, lab, key, lmax, k)
        else:
            out = engine.refine_level(cm, gb, ev, lab, key, lmax, taus,
                                      k, patience, max_inner,
                                      move_fn=var.move)
        if relayout:
            out = out[inv_perm[0]]
        return out[None]

    sh = P("pe", None)
    return jax.jit(shard_map(
        refine_halo, mesh=mesh,
        in_specs=(sh, sh, sh, sh, sh, sh, sh, sh, sh, P("pe"), sh, P(), P(),
                  P()),
        out_specs=sh,
    ))


def make_refine_level_halo(mesh, hsg, k, *, rounds_taus, patience=12,
                           max_inner=64, gain="jnp", interpret=None,
                           uniform_mode="global", variant="jet",
                           relayout=False):
    """Fused level refinement over a :class:`HaloShardedGraph`.

    ``uniform_mode="global"`` (default) draws rebalance randomness in the
    shared global-vertex-space stream — the determinism-contract setting;
    ``"fold"`` keeps the O(n_local) per-gid fold-in stream for scale runs.
    ``variant`` names the registered move-generation rule; lp-mode variants
    run ``engine.lp_level`` over the halo protocol (interface-only
    exchange applies to the LP baseline too).

    ``gain`` also selects the halo *move-application* backend: under
    ``"pallas"``/``"auto"`` the greedy rebalancer's move scatter runs
    through the fused gid-compare kernel (``repro.kernels.halo``, its own
    VMEM envelope — oversize shapes fall back to the XLA path), so the
    existing backend matrix exercises both renderings with no extra axis.
    ``relayout=True`` makes ``run`` take and return *block-layout* labels,
    fusing the halo↔block conversions into the level program (the sharded
    V-cycle's setting); the default keeps the halo-layout interface.
    ``run.backends`` names the gain and halo backends the fallback rules
    resolved.
    """
    from repro.kernels.halo import resolve_halo

    resolve_variant(variant)
    max_deg = (sharded_max_deg(hsg.src, hsg.head_gid, hsg.n_local)
               if _need_max_deg(gain) else None)
    gain_kind = resolve_gain(gain, k, max_deg)
    halo_kind = resolve_halo(gain, hsg.P * engine.GREEDY_NCAND)
    fn = _halo_level_fn(
        mesh, k, hsg.n_local, hsg.n_real, hsg.P, hsg.h_local, patience,
        max_inner, gain_kind, max_deg if gain_kind == "pallas" else None,
        interpret, uniform_mode, variant, halo_kind, relayout)
    taus = jnp.asarray(rounds_taus, jnp.float32)

    def run(lab_sh, key, lmax):
        _count_dispatch("halo")
        return fn(hsg.src, hsg.dst_code, hsg.head_gid, hsg.ew, hsg.nw,
                  hsg.my_gid, hsg.owned, hsg.inv_perm, hsg.perm_loc,
                  hsg.gstart, lab_sh, key, jnp.float32(lmax), taus)

    run.backends = _level_backends(gain_kind, halo_kind, variant)
    return run
