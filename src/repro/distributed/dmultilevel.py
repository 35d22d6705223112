"""Distributed multilevel driver.

The full V-cycle stays on device (paper §2 + DESIGN.md §2/§3), under either
comm protocol:

  coarsen ↓   dcoarsen.py — sharded LP clustering + contraction under
              shard_map, with a bucketed all_to_all edge reshuffle; each
              coarse level is born sharded, the fine graph is never gathered.
              With halo=True every level also emits its interface-only halo
              metadata (halo.halo_from_sharded: a per-PE ownership compare,
              device-side interface-first sort and one all_gather of the
              inverse permutations — only the h_local scalar joins the 3
              per-level scalars crossing to the host)
  initial     the (small, ≤ max(512, 16k)-vertex) coarsest graph is
              centralised — exactly where dKaMinPar also replicates — and
              seeded with the multi-restart greedy + refine of core.initial
  uncoarsen ↑ one all_gather of coarse labels per level (duncoarsen); the
              labels route straight into the level's refinement layout —
              baseline all-gather BSP, or the halo layout via a per-PE
              device-side permutation gather (halo.block_labels_to_halo) —
              and the fused level program refines in place

``coarsen="host"`` keeps the original centralised coarsening as a debugging
fallback (level graphs are built on the host and re-sharded per level); both
paths produce bit-identical partitions from the same seed on integer-weight
graphs — with or without halo=True — which is how the sharded path is
tested.  The old "halo implies host coarsening" restriction is gone: the
halo layout is derived from each sharded level directly.
"""

from __future__ import annotations

import dataclasses
import itertools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.checkpoint import vcycle as vc
from repro.core import coarsen as C
from repro.core.config import UNSET, PartitionConfig, resolve_config
from repro.core.graph import PAD, Graph
from repro.core.initial import initial_partition
from repro.core.multilevel import level_trace_entry
from repro.core.partition import exact_edge_cut, imbalance, l_max
from repro.core.refine import temperature_schedule
from repro.distributed.dcoarsen import dcoarsen_hierarchy, duncoarsen
from repro.distributed.dgraph import (
    ShardedGraph,
    labels_from_sharded,
    labels_to_sharded,
    shard_graph,
    sharded_edge_cut,
    sharded_imbalance,
    sharded_to_graph,
)
from repro.refine.drivers import (
    level_tolerances,
    make_refine_level_halo,
    make_refine_level_sharded,
)
from repro.core.multilevel import _level_w_fracs
from repro.refine.schedule import ToleranceSchedule
from repro.refine.variants import Variant
from repro.sharding.compat import make_mesh


# per-process id of a dpartition call, on its span: the spans of one job
# share it
_CALL_IDS = itertools.count()


@dataclasses.dataclass(frozen=True)
class DPartitionResult:
    labels: jax.Array
    cut: float
    imbalance: float
    levels: int
    P: int
    # phase wall times in seconds, only populated by dpartition(timing=True)
    # (timing adds block_until_ready syncs at the phase boundaries, so it is
    # opt-in; keys: coarsen_s, init_s, refine_s — read by bench/metrics)
    timings: dict | None = None
    # per-level tolerances eps_l actually targeted, coarsest → finest
    level_eps: tuple = ()
    # per-level {n, eps, imbalance} after each level's refinement
    # (coarsest → finest), populated by dpartition(trace_levels=True)
    level_trace: tuple | None = None
    # committed snapshot step the V-cycle restarted from (None = fresh run)
    resume_step: int | None = None
    # per refined level (coarsest → finest): {n, gain, halo, afterburner}
    # — the backends the envelope rules resolved (halo None under the
    # all-gather protocol), so a fallback from the Pallas kernels to jnp is
    # visible, and the edge layout of the afterburner ("dense" beside the
    # Pallas gain, "slot" beside jnp, None for lp variants)
    level_backends: tuple = ()
    # this call's {"host_reads": {site: n}, "dispatches": {program: n}}
    # (repro.telemetry), always populated
    counters: dict = dataclasses.field(default_factory=dict)


class _PhaseTimer:
    """Opens a host span per phase (``coarsen``, ``init``, ``refine``) and,
    when enabled, accumulates the phase's wall time (``<phase>_s``) around
    explicit sync points; disabled, it adds no sync to the V-cycle."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.acc: dict[str, float] = {}
        self._t0 = 0.0
        self._phase = ""
        self._span = None

    def start(self, phase: str, sync=None):
        if self.enabled:
            if sync is not None:
                jax.block_until_ready(sync)
            self._t0 = time.perf_counter()
        self._phase = phase
        self._span = telemetry.span(phase)
        self._span.__enter__()

    def stop(self, sync=None):
        if self.enabled:
            if sync is not None:
                jax.block_until_ready(sync)
            key = f"{self._phase}_s"
            self.acc[key] = self.acc.get(key, 0.0) + (
                time.perf_counter() - self._t0)
        self._span.__exit__(None, None, None)
        self._span = None

    def result(self) -> dict | None:
        return dict(self.acc) if self.enabled else None


def make_pe_mesh(P: int | None = None):
    if P is None:
        P = jax.device_count()
    mesh = make_mesh((P,), ("pe",))
    return mesh, P


def _dl_max(sg: ShardedGraph, k: int, eps: float):
    """L_max from the sharded level — same value as l_max(g, k, eps) (total
    node weight is invariant under contraction)."""
    return (1.0 + eps) * jnp.ceil(jnp.sum(sg.nw) / k)


def _dimbalance(sg: ShardedGraph, lab_sh, k: int) -> float:
    """Imbalance of a sharded labelling — padding slots carry zero weight,
    so they contribute nothing to the block weights."""
    return telemetry.host_read("imbalance", sharded_imbalance(sg, lab_sh, k),
                               float)


def _drefine_sharded(mesh, sg: ShardedGraph, lab_sh, k, lmax, key,
                     var: Variant, patience, max_inner, gain="jnp", hsg=None,
                     halo_uniform="global"):
    """Refine one already-sharded level in place (labels stay sharded).

    The whole level is ONE fused dispatch (``repro.refine.drivers``): the
    temperature loop and the inner (Jet → rebalance → patience) loop run
    device-resident, instead of one dispatch per round.  ``var`` is the
    resolved refinement variant — its move-generation rule (or the lp level
    program) runs over whichever comm backend the level uses.  With ``hsg``
    set, the level runs under the interface-only halo protocol: labels
    convert to the interface-first layout with a per-PE device gather,
    refine, and convert back — still one dispatch for the level program.
    Returns ``(labels, backends)`` — the resolved gain/halo backends."""
    taus = temperature_schedule(var.rounds)
    if hsg is not None:
        # relayout=True fuses the halo↔block label conversions into the
        # level program itself (repro.refine.drivers._halo_level_fn): the
        # run takes and returns block-layout labels and the permutation
        # gathers compile into the one level dispatch — the old standalone
        # block_labels_to_halo/from_halo dispatches are gone from this path
        run = make_refine_level_halo(
            mesh, hsg, k, rounds_taus=taus,
            patience=patience, max_inner=max_inner, gain=gain,
            uniform_mode=halo_uniform, variant=var.name, relayout=True)
    else:
        run = make_refine_level_sharded(
            mesh, sg, k, rounds_taus=taus, patience=patience,
            max_inner=max_inner, gain=gain, variant=var.name)
    return run(lab_sh, key, lmax), run.backends


def _drefine_level(mesh, g: Graph, labels, k, eps, key, var: Variant,
                   patience, max_inner, halo: bool = False, gain="jnp",
                   halo_uniform="global"):
    """Host-path level refinement: shard the level graph, refine, gather.
    Returns ``(labels, backends)`` like :func:`_drefine_sharded`."""
    P_ = mesh.devices.size
    lmax = l_max(g, k, eps)

    if halo:
        # interface-only exchange fast path (§Perf cell 1, paper's ghost
        # protocol), same fused engine over the HaloComm backend
        from repro.distributed.halo import (
            halo_labels_from_sharded,
            halo_labels_to_sharded,
            shard_graph_halo,
        )

        hsg, perm = shard_graph_halo(g, P_)
        lab_sh = halo_labels_to_sharded(hsg, perm, labels)
        run = make_refine_level_halo(
            mesh, hsg, k, rounds_taus=temperature_schedule(var.rounds),
            patience=patience, max_inner=max_inner, gain=gain,
            uniform_mode=halo_uniform, variant=var.name)
        lab_sh = run(lab_sh, key, lmax)
        return halo_labels_from_sharded(hsg, perm, lab_sh), run.backends

    sg = shard_graph(g, P_, mesh=mesh)
    lab_sh = labels_to_sharded(sg, labels)
    lab_sh, backends = _drefine_sharded(mesh, sg, lab_sh, k, lmax, key, var,
                                        patience, max_inner, gain=gain)
    return labels_from_sharded(sg, lab_sh), backends


def _dpartition_host_coarsen(mesh, g, k, eps, key, k_coarse, k_init, var,
                             coarsen_until, patience, max_inner, halo, gain,
                             halo_uniform, timer, sched, trace_levels,
                             policy=None, resume=None, fp=None):
    """Fallback: centralised coarsening, per-level re-sharded refinement."""
    timer.start("coarsen")
    levels, coarsest = C.coarsen_hierarchy(g, k, k_coarse,
                                           coarsen_until=coarsen_until)
    timer.stop(coarsest.nw)
    n_levels = len(levels) + 1
    level_graphs = [coarsest] + [fine for fine, _ in reversed(levels)]
    mappings = [mapping for _, mapping in reversed(levels)]
    w_fracs = _level_w_fracs(sched, [lg.nw for lg in level_graphs])
    eps_l = level_tolerances(sched, eps, n_levels, k, w_fracs=w_fracs)

    start, resume_step = 0, None
    if resume is not None:
        resume_step = vc.find_resume_step(resume, fp)
    if resume_step is not None:
        # step s holds post-rung-(s−1) labels on level s−1 (s=0: initial
        # partition on the coarsest); restore_resharded onto THIS mesh is
        # the elastic path — the writing run's P may have differed
        at = level_graphs[max(0, resume_step - 1)]
        lab_h, key_h = vc.restore_step(resume, resume_step, at.n, mesh=mesh)
        labels, key = jnp.asarray(lab_h), jnp.asarray(key_h)
        start = resume_step
    else:
        timer.start("init")
        labels = initial_partition(coarsest, k, eps, k_init)
        timer.stop(labels)
        if policy is not None:
            vc.save_step(policy, 0, labels, key, fp)

    trace: list[dict] = []
    backends: list[dict] = []

    def _record(lvl_g, lab, e):
        if trace_levels:
            trace.append(level_trace_entry(lvl_g.n, e,
                                           imbalance(lvl_g, lab, k)))

    timer.start("refine")
    for j in range(start, n_levels):
        if j > 0:
            labels = labels[mappings[j - 1]]
        key, sub = jax.random.split(key)
        with telemetry.span("refine/level", level=j,
                            n=level_graphs[j].n) as sp:
            labels, used = _drefine_level(
                mesh, level_graphs[j], labels, k, eps_l[j], sub, var,
                patience, max_inner, halo=halo, gain=gain,
                halo_uniform=halo_uniform)
            sp.set_metadata(gain=used["gain"])
        backends.append(dict(n=level_graphs[j].n, **used))
        _record(level_graphs[j], labels, eps_l[j])
        if policy is not None and policy.want_step(j, n_levels):
            vc.save_step(policy, j + 1, labels, key, fp)
    timer.stop(labels)
    return labels, n_levels, eps_l, trace, resume_step, backends


def _dpartition_sharded_coarsen(mesh, g, k, eps, key, k_coarse, k_init,
                                var, coarsen_until, patience, max_inner,
                                halo, gain, halo_uniform, timer, sched,
                                trace_levels, policy=None, resume=None,
                                fp=None):
    """On-device V-cycle: graph is sharded once; every level stays sharded.

    With halo=True the hierarchy emits device-derived halo metadata per
    level and every refinement runs under the interface-only protocol — the
    fully on-device halo V-cycle (no per-level host gather of the graph).
    ``g`` may already be a :class:`ShardedGraph` (the out-of-core ingest
    path) — it is used as-is instead of re-sharding a host Graph."""
    P_ = mesh.devices.size
    with telemetry.span("shard"):
        sg0 = (g if isinstance(g, ShardedGraph)
               else shard_graph(g, P_, mesh=mesh))
    timer.start("coarsen", sg0.nw)
    if halo:
        levels, coarsest, halos = dcoarsen_hierarchy(
            mesh, sg0, k, k_coarse, coarsen_until=coarsen_until, halo=True)
    else:
        levels, coarsest = dcoarsen_hierarchy(mesh, sg0, k, k_coarse,
                                              coarsen_until=coarsen_until)
        halos = [None] * (len(levels) + 1)
    timer.stop(coarsest.nw)
    n_levels = len(levels) + 1
    # refinement-order level list: coarsest, then levels[i][0] fine graphs
    # (levels[i][2] is level_sgs[depth-1] — the coarse side of contraction i)
    level_sgs = [coarsest] + [levels[i][0]
                              for i in reversed(range(len(levels)))]
    # per-level w_max/c(V) from the sharded nw slices (padding weighs 0, so
    # the fraction matches the host hierarchy's bit-for-bit)
    w_fracs = _level_w_fracs(sched, [sg.nw for sg in level_sgs])
    eps_l = level_tolerances(sched, eps, n_levels, k, w_fracs=w_fracs)

    start, resume_step = 0, None
    if resume is not None:
        resume_step = vc.find_resume_step(resume, fp)
    if resume_step is not None:
        # snapshots hold GLOBAL-layout labels; re-shard onto the recomputed
        # level — elastic resume (different P) falls out of the layout
        at = level_sgs[max(0, resume_step - 1)]
        lab_h, key_h = vc.restore_step(resume, resume_step, at.n_real,
                                       mesh=mesh)
        lab_sh = labels_to_sharded(at, jnp.asarray(lab_h))
        key = jnp.asarray(key_h)
        start = resume_step
    else:
        # initial partitioning on the (small) centralised coarsest graph
        timer.start("init")
        gc = sharded_to_graph(coarsest)
        labels = initial_partition(gc, k, eps, k_init)
        lab_sh = labels_to_sharded(coarsest, labels)
        timer.stop(lab_sh)
        if policy is not None:
            vc.save_step(policy, 0, labels_from_sharded(coarsest, lab_sh),
                         key, fp)

    trace: list[dict] = []
    backends: list[dict] = []

    def _record(lvl_sg, lab, e):
        if trace_levels:
            trace.append(level_trace_entry(
                lvl_sg.n_real, e, sharded_imbalance(lvl_sg, lab, k)))

    timer.start("refine")
    for j in range(start, n_levels):
        if j == 0:
            sg_j, hs = coarsest, halos[-1]
        else:
            i = len(levels) - j
            fine_sg, map_sh, coarse_sg = levels[i]
            lab_sh = duncoarsen(mesh, fine_sg, map_sh, coarse_sg, lab_sh)
            sg_j, hs = fine_sg, halos[i]
        key, sub = jax.random.split(key)
        with telemetry.span("refine/level", level=j, n=sg_j.n_real) as sp:
            lab_sh, used = _drefine_sharded(
                mesh, sg_j, lab_sh, k, _dl_max(sg_j, k, eps_l[j]), sub, var,
                patience, max_inner, gain=gain, hsg=hs,
                halo_uniform=halo_uniform)
            sp.set_metadata(gain=used["gain"])
        backends.append(dict(n=sg_j.n_real, **used))
        _record(sg_j, lab_sh, eps_l[j])
        if policy is not None and policy.want_step(j, n_levels):
            vc.save_step(policy, j + 1, labels_from_sharded(sg_j, lab_sh),
                         key, fp)
    timer.stop(lab_sh)

    return labels_from_sharded(sg0, lab_sh), n_levels, eps_l, trace, \
        resume_step, backends


def dpartition(
    g: Graph | ShardedGraph,
    k: int | None = UNSET,
    P: int | None = None,
    eps: float | None = UNSET,
    seed: int = 0,
    refiner: str | None = UNSET,
    coarsen: str | None = "sharded",
    coarsen_until: int | None = UNSET,
    patience: int | None = UNSET,
    max_inner: int | None = UNSET,
    halo: bool = False,
    gain: str | None = UNSET,
    halo_uniform: str = "global",
    timing: bool = False,
    schedule: str | ToleranceSchedule | None = UNSET,
    eps_coarse: float | None = UNSET,
    trace_levels: bool = False,
    ckpt=UNSET,
    resume: str | None = None,
    config: PartitionConfig | None = None,
) -> DPartitionResult:
    """Distributed multilevel partition; ``halo=True`` composes with either
    coarsening path (the halo layout is derived per level from the sharded
    level itself under ``coarsen="sharded"``).  Static partitioning knobs
    live in one frozen :class:`PartitionConfig` (``config=``); the loose
    kwargs are the bit-identical thin facade over it, while placement /
    execution options (``P``, ``coarsen``, ``halo``, ``halo_uniform``,
    ``timing``, ``trace_levels``) stay loose — they describe *where and
    how* this call runs, not *what* partition it computes.  ``refiner``
    names a registered refinement variant (``repro.refine.variants``;
    unknown names raise ``ValueError`` listing the registry).
    ``halo_uniform`` picks the
    halo rebalance stream: ``"global"`` (default, the cross-backend
    determinism contract) or ``"fold"`` (O(n_local) memory for scale runs;
    P-invariant but its own stream — see DESIGN.md §2).  ``timing=True``
    populates ``DPartitionResult.timings`` with per-phase wall seconds
    (coarsen_s / init_s / refine_s) at the cost of phase-boundary syncs —
    the benchmark harness's hook (``bench/metrics``).  Every call opens
    host spans (``repro.telemetry``) and returns its own host-read and
    dispatch counts in ``DPartitionResult.counters``.

    ``schedule`` names the per-level imbalance-tolerance schedule
    (``repro.refine.schedule``: ``constant`` / ``geometric`` / ``snap``) —
    coarse levels rebalance against their own ``eps_l ≥ eps``, only the
    finest level is held to the final ``eps``; the per-level value rides
    into the fused level program's traced ``lmax`` scalar, so a
    non-constant schedule adds no dispatches.  ``trace_levels=True``
    records per-level {n, eps, imbalance} in
    ``DPartitionResult.level_trace`` (one host sync per level — the
    property suite's hook).

    ``g`` may be a :class:`ShardedGraph` — the out-of-core ingest path
    (``repro.graphs.ingest.ingest_sharded``): the global edge list is never
    materialised on the host, the V-cycle runs straight off the device
    shards (``coarsen="sharded"`` only) and the final cut/imbalance come
    from the sharded layout.  ``ckpt`` (a
    :class:`repro.checkpoint.CheckpointPolicy`, or via ``config=``)
    snapshots the V-cycle state after initial partitioning and each
    refinement rung; ``resume=<ckpt_dir>`` restores the latest committed
    snapshot and continues — bit-identical to the uninterrupted run,
    including onto a different device count (snapshots hold global-layout
    labels; partitions are P-invariant)."""
    cfg = resolve_config(config, where="dpartition", k=k, eps=eps,
                         refiner=refiner, schedule=schedule,
                         eps_coarse=eps_coarse, gain=gain, patience=patience,
                         max_inner=max_inner, coarsen_until=coarsen_until,
                         ckpt=ckpt)
    var, sched = cfg.variant(), cfg.tolerance_schedule()
    k, eps, gain = cfg.k, cfg.eps, cfg.gain
    patience, max_inner = cfg.patience, cfg.max_inner
    coarsen_until = cfg.coarsen_until
    if coarsen is None:
        coarsen = "sharded"  # old auto default; halo no longer forces "host"
    if coarsen not in ("sharded", "host"):
        raise ValueError(f"coarsen must be 'sharded' or 'host', got {coarsen!r}")
    sharded_in = isinstance(g, ShardedGraph)
    if sharded_in:
        if coarsen != "sharded":
            raise ValueError(
                "coarsen='host' needs a centralised Graph; a ShardedGraph "
                "input (out-of-core ingest) runs under coarsen='sharded'")
        if P is None:
            P = g.P
        elif P != g.P:
            raise ValueError(
                f"P={P} does not match the ingested ShardedGraph's P={g.P}; "
                f"re-ingest with ingest_sharded(manifest, P={P})")
    mesh, P_ = make_pe_mesh(P)
    key = jax.random.PRNGKey(seed)
    k_coarse, k_init, key = jax.random.split(key, 3)
    timer = _PhaseTimer(timing)

    with telemetry.span("dpartition", call=next(_CALL_IDS)), \
            telemetry.record() as rec:
        policy = cfg.ckpt
        fp = None
        if policy is not None or resume is not None:
            if sharded_in:
                n_g = g.n_real
                m_live = telemetry.host_read("m_live", jnp.sum(g.dst != PAD))
            else:
                n_g = g.n
                m_live = telemetry.host_read(
                    "m_live", g.row_ptr, lambda rp: int(np.asarray(rp)[-1]))
            fp = vc.fingerprint(cfg, seed, n_g, m_live)

        if coarsen == "host":
            labels, n_levels, eps_l, trace, resume_step, backends = \
                _dpartition_host_coarsen(
                    mesh, g, k, eps, key, k_coarse, k_init, var,
                    coarsen_until, patience, max_inner, halo, gain,
                    halo_uniform, timer, sched, trace_levels, policy=policy,
                    resume=resume, fp=fp)
        else:
            labels, n_levels, eps_l, trace, resume_step, backends = \
                _dpartition_sharded_coarsen(
                    mesh, g, k, eps, key, k_coarse, k_init, var,
                    coarsen_until, patience, max_inner, halo, gain,
                    halo_uniform, timer, sched, trace_levels, policy=policy,
                    resume=resume, fp=fp)

        if sharded_in:
            lab_fin = labels_to_sharded(g, labels)
            cut = sharded_edge_cut(g, lab_fin)
            imb = _dimbalance(g, lab_fin, k)
        else:
            cut = exact_edge_cut(g, labels)
            imb = telemetry.host_read("imbalance", imbalance(g, labels, k),
                                      float)
    return DPartitionResult(
        labels=labels,
        cut=cut,
        imbalance=imb,
        levels=n_levels,
        P=P_,
        timings=timer.result(),
        level_eps=eps_l,
        level_trace=tuple(trace) if trace_levels else None,
        resume_step=resume_step,
        level_backends=tuple(backends),
        counters=rec.as_dict(),
    )
