"""Multilevel V-cycle driver: coarsen → initial partition → uncoarsen+refine.

``refiner`` names a registered refinement variant
(``repro.refine.variants``): ``jet`` / ``jetlp`` / ``jet_h`` / ``jet_v`` /
``lp``, plus the paper-configuration aliases ``d4xjet`` (= jet, 4
temperature rounds, the default), ``djet`` (= jet, 1 round), ``djet_v``
(= jet_v, 1 round) and ``dlp`` (= lp).

``schedule`` names a per-level imbalance-tolerance schedule
(``repro.refine.schedule``): ``constant`` (default) / ``geometric`` /
``snap`` — coarse levels refine against their own ``eps_l ≥ eps`` and only
the finest level is held to the final ``eps``.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.core import coarsen as C
from repro.core.config import UNSET, PartitionConfig, resolve_config
from repro.core.graph import Graph
from repro.core.initial import initial_partition
from repro.core.partition import exact_edge_cut, imbalance
from repro.core.refine import jet_refine, lp_refine_level
from repro.refine.drivers import level_tolerances
from repro.refine.schedule import ToleranceSchedule, weight_frac


def _level_w_fracs(sched, ordered_nws):
    """Coarsest-first per-level ``w_max/c(V)`` fractions for the
    ``adaptive`` schedule — ``None`` for every other mode so non-adaptive
    V-cycles add no host syncs at setup."""
    if sched.mode != "adaptive":
        return None
    return tuple(weight_frac(nw) for nw in ordered_nws)
from repro.refine.variants import Variant

Refiner = str  # a registered variant or alias name — see repro.refine.variants


def level_trace_entry(n, eps, imb) -> dict:
    """The single home of the per-level trace record shape
    (``PartitionResult.level_trace`` / ``DPartitionResult.level_trace``;
    the P-invariance tests compare these dicts for exact equality across
    paths, so every recorder must build them here)."""
    return {"n": int(n), "eps": float(eps),
            "imbalance": telemetry.host_read("level_imbalance", imb, float)}


@dataclasses.dataclass(frozen=True)
class PartitionResult:
    labels: jax.Array
    cut: float
    imbalance: float
    levels: int
    # per-level tolerances eps_l actually targeted, coarsest → finest
    level_eps: tuple = ()
    # per-level {n, eps, imbalance} after each level's refinement
    # (coarsest → finest), populated by partition(trace_levels=True)
    level_trace: tuple | None = None
    # checkpoint step this run restored (None = ran from scratch); with a
    # resume the labels are bit-identical to the uninterrupted run, but
    # level_trace only covers the rungs actually re-executed
    resume_step: int | None = None


def _refine(g: Graph, labels, k, eps, key, var: Variant, patience: int,
            max_inner: int, gain: str = "jnp"):
    if var.mode == "lp":
        return lp_refine_level(g, labels, k, eps, key, gain=gain)
    return jet_refine(g, labels, k, eps, key, rounds=var.rounds,
                      patience=patience, max_inner=max_inner, gain=gain,
                      variant=var.name)


def partition(
    g: Graph,
    k: int | None = UNSET,
    eps: float | None = UNSET,
    seed: int = 0,
    refiner: Refiner | None = UNSET,
    coarsen_until: int | None = UNSET,
    patience: int | None = UNSET,
    max_inner: int | None = UNSET,
    gain: str | None = UNSET,
    schedule: str | ToleranceSchedule | None = UNSET,
    eps_coarse: float | None = UNSET,
    trace_levels: bool = False,
    ckpt=UNSET,
    resume: str | None = None,
    config: PartitionConfig | None = None,
) -> PartitionResult:
    """Full multilevel partition of ``g`` into ``k`` blocks.

    All static knobs live in one frozen :class:`PartitionConfig`
    (``repro.core.config``); pass one via ``config=`` or use the loose
    kwargs — a thin facade that overrides the corresponding config fields
    and is bit-identical to the config form (tests/test_config.py).
    Facade kwargs default to the ``UNSET`` sentinel, so an *explicit*
    ``None`` overrides too: ``partition(g, config=cfg, eps_coarse=None)``
    really clears ``cfg.eps_coarse``.

    ``refiner`` names a registered refinement variant (see module
    docstring; unknown names raise ``ValueError`` listing the registry).
    ``gain`` selects the refinement gain backend ("jnp", "pallas" or
    "auto") — see ``repro.refine``; partitions are bit-identical across
    backends on integer-weight graphs.  ``schedule`` names the per-level
    imbalance-tolerance schedule (``repro.refine.schedule``); the initial
    partition and the finest level always target the final ``eps``.
    ``trace_levels=True`` records per-level imbalance after each level's
    refinement in ``PartitionResult.level_trace`` (adds one host sync per
    level — the property suite's hook).

    ``config.ckpt`` (or the ``ckpt=`` facade kwarg — a
    :class:`repro.checkpoint.CheckpointPolicy`) snapshots the V-cycle
    state after initial partitioning and after each uncoarsening rung;
    ``resume=ckpt_dir`` restores the latest intact snapshot and continues
    to a **bit-identical** final partition (repro.checkpoint.vcycle — the
    hierarchy is recomputed deterministically, only labels + RNG key are
    restored).  An empty/absent resume dir starts from scratch."""
    from repro.checkpoint import vcycle as vc

    cfg = resolve_config(config, where="partition", k=k, eps=eps,
                         refiner=refiner, schedule=schedule,
                         eps_coarse=eps_coarse, gain=gain, patience=patience,
                         max_inner=max_inner, coarsen_until=coarsen_until,
                         ckpt=ckpt)
    var, sched = cfg.variant(), cfg.tolerance_schedule()
    k, eps, gain = cfg.k, cfg.eps, cfg.gain
    patience, max_inner = cfg.patience, cfg.max_inner
    coarsen_until = cfg.coarsen_until
    policy = cfg.ckpt
    key = jax.random.PRNGKey(seed)
    k_coarse, k_init, key = jax.random.split(key, 3)

    levels, coarsest = C.coarsen_hierarchy(g, k, k_coarse, coarsen_until=coarsen_until)
    n_levels = len(levels) + 1
    w_fracs = _level_w_fracs(
        sched, [coarsest.nw] + [f.nw for f, _ in reversed(levels)])
    eps_l = level_tolerances(sched, eps, n_levels, k, w_fracs=w_fracs)

    # rung j refines level_graphs[j]; rung j > 0 first projects through
    # mappings[j-1] (identical to the old reversed(levels) loop)
    level_graphs = [coarsest] + [fine for fine, _ in reversed(levels)]
    mappings = [mapping for _, mapping in reversed(levels)]

    fp = (vc.fingerprint(cfg, seed, g.n, telemetry.host_read(
        "m_live", g.row_ptr, lambda rp: int(np.asarray(rp)[-1])))
          if (policy or resume) else None)
    start, resume_step = 0, None
    if resume is not None:
        resume_step = vc.find_resume_step(resume, fp)
    if resume_step is not None:
        n_at = level_graphs[max(0, resume_step - 1)].n
        lab_h, key_h = vc.restore_step(resume, resume_step, n_at)
        labels, key = jnp.asarray(lab_h), jnp.asarray(key_h)
        start = resume_step
    else:
        labels = initial_partition(coarsest, k, eps, k_init)
        if policy is not None:
            vc.save_step(policy, 0, labels, key, fp)

    trace: list[dict] = []

    def _record(lvl_g, lab, e):
        if trace_levels:
            trace.append(level_trace_entry(lvl_g.n, e,
                                           imbalance(lvl_g, lab, k)))

    for j in range(start, n_levels):
        if j > 0:
            labels = labels[mappings[j - 1]]  # project to the finer level
        key, sub = jax.random.split(key)
        labels = _refine(level_graphs[j], labels, k, eps_l[j], sub, var,
                         patience, max_inner, gain)
        _record(level_graphs[j], labels, eps_l[j])
        if policy is not None and policy.want_step(j, n_levels):
            vc.save_step(policy, j + 1, labels, key, fp)

    return PartitionResult(
        labels=labels,
        cut=exact_edge_cut(g, labels),
        imbalance=telemetry.host_read("imbalance", imbalance(g, labels, k),
                                      float),
        levels=n_levels,
        level_eps=eps_l,
        level_trace=tuple(trace) if trace_levels else None,
        resume_step=resume_step,
    )


def _lmax_batch(nw_stack, eps_per_slot, k: int):
    """(B,) per-slot L_max over a padded nw stack — element-for-element the
    same fp32 ops as ``partition.l_max`` on the unpadded graph (padding
    vertices weigh 0; integer fp32 sums are exact), so the batched engine
    targets bit-identical balance bounds."""
    one_plus = jnp.asarray([1.0 + e for e in eps_per_slot], jnp.float32)
    return one_plus * jnp.ceil(jnp.sum(nw_stack, axis=1) / k)


def seed_list(graphs, seeds, seed, where: str = "partition_batch") -> list:
    """Resolve the per-graph seed list at the API boundary.

    A mismatched ``seeds=`` must fail here with a clear ValueError — not
    deep inside the key chain — and the check runs *before* any early
    return, so ``partition_batch([], seeds=[1])`` is an error, not a silent
    ``[]``.  The serving scheduler routes its own ``seeds=`` override
    through this same helper (the check is inherited, not duplicated)."""
    if seeds is None:
        return [seed] * len(graphs)
    try:
        seeds = list(seeds)
    except TypeError:
        raise ValueError(
            f"{where}: seeds= must be an iterable with one seed per graph, "
            f"got {type(seeds).__name__}") from None
    if len(seeds) != len(graphs):
        raise ValueError(
            f"{where}: seeds has {len(seeds)} entries for "
            f"{len(graphs)} graphs — pass exactly one seed per graph")
    return seeds


# --------------------------------------------------------------------------
# batched-engine phases.  partition_batch = plan → init dispatch → winner
# select → rung dispatches → finalize, and the serving runner
# (repro.serve.runner) replays the SAME helpers over several flushed buckets
# with all device dispatches enqueued before any result is read — so the
# multi-bucket path is bit-identical to partition_batch by construction.
# --------------------------------------------------------------------------


def coalesce_slots(graphs, seeds, coalesce: bool):
    """Request coalescing: identical requests (same :class:`Graph` *object*
    + seed — the serving fan-out pattern) share one engine slot.  Returns
    ``(slot_of, pairs)`` with ``pairs`` the unique (graph, seed) work items
    and ``slot_of[i]`` the slot index serving request ``i``.  Equal-content
    but distinct Graph objects intentionally stay separate slots (batch
    invariance makes their results identical anyway)."""
    slot_of, uniq, pairs = [], {}, []
    for g, s in zip(graphs, seeds):
        kk = (id(g), s) if coalesce else len(pairs)
        if kk not in uniq:
            uniq[kk] = len(pairs)
            pairs.append((g, s))
        slot_of.append(uniq[kk])
    return slot_of, pairs


def plan_request(g: Graph, s: int, k: int, sched, eps: float,
                 coarsen_until: int | None) -> dict:
    """Host coarsening + tolerance resolution for ONE request, replaying
    ``partition()``'s exact key chain.  The returned dict is immutable (the
    serving buffer pool caches it per request signature — coarsening is
    deterministic, so a cached plan IS the recomputed plan); per-execution
    mutable state is layered on by :func:`exec_state`."""
    key = jax.random.PRNGKey(s)
    k_coarse, k_init, key = jax.random.split(key, 3)
    levels, coarsest = C.coarsen_hierarchy(g, k, k_coarse,
                                           coarsen_until=coarsen_until)
    n_levels = len(levels) + 1
    w_fracs = _level_w_fracs(
        sched, [coarsest.nw] + [f.nw for f, _ in reversed(levels)])
    return {
        "g": g, "key0": key, "k_init": k_init,
        # uncoarsening rungs: rung 0 = coarsest, rung j>0 = (fine,
        # mapping) = reversed(levels)[j-1] — partition()'s loop order
        "rungs": tuple(reversed(levels)), "coarsest": coarsest,
        "n_levels": n_levels,
        "eps_l": level_tolerances(sched, eps, n_levels, k, w_fracs=w_fracs),
    }


def exec_state(plan: dict) -> dict:
    """Fresh mutable execution state over a (possibly cached) plan."""
    return {**plan, "key": plan["key0"], "trace": []}


def _make_batched(graphs, n_bucket, m_bucket, batched=None):
    """Assemble the bucket batch — through ``batched`` (the serving buffer
    pool's cached-slot hook, same bucket rule) when given, else a fresh
    ``from_graphs``.  ``None`` buckets mean the :func:`from_graphs`
    defaults (bucket of the batch maxima)."""
    from repro.graphs.batch import from_graphs

    if batched is not None:
        return batched(graphs, n_bucket, m_bucket)
    return from_graphs(graphs, n_bucket=n_bucket, m_bucket=m_bucket)


def init_dispatch(st, k: int, eps: float, batched=None):
    """Enqueue the batched initial-partitioning dispatch for one bucket's
    work items; returns DEVICE arrays (no host sync — the multi-bucket
    runner enqueues every bucket before reading any)."""
    from repro.refine.drivers import initial_partition_batched

    bg0 = _make_batched([s["coarsest"] for s in st], None, None, batched)
    return initial_partition_batched(
        bg0, k, jnp.stack([s["k_init"] for s in st]),
        _lmax_batch(bg0.nw, [eps] * len(st), k), as_numpy=False)


def init_select(st, labs, cuts, ovs) -> None:
    """Host-side winner selection (the solo first-best-balanced rule) —
    this is where the init results are synced."""
    import numpy as np

    labs, cuts, ovs = np.asarray(labs), np.asarray(cuts), np.asarray(ovs)
    for i, s in enumerate(st):
        best, best_cut = None, float("inf")
        for r in range(labs.shape[1]):  # the solo first-best-balanced rule
            if float(ovs[i, r]) <= 0 and float(cuts[i, r]) < best_cut:
                best, best_cut = labs[i, r], float(cuts[i, r])
        if best is None:  # all restarts imbalanced — take the last anyway
            best = labs[i, -1]
        s["labels"] = jnp.asarray(best[: s["coarsest"].n])


def refine_rung(st, j: int, k: int, var: Variant, taus, patience: int,
                max_inner: int, gain: str, trace_levels: bool = False,
                batched=None, donate: bool = False, pad_to: int | None = None,
                bucket_hook=None) -> None:
    """Enqueue rung ``j``'s batched level dispatch for one bucket's work
    items (projection, padding, lmax and the level program are all device
    ops — nothing here blocks unless ``trace_levels`` asks for the
    per-level host sync).

    ``pad_to`` / ``bucket_hook`` are the serving path's steady-state hooks
    (``repro.serve.runner``): hierarchy depth and per-level graph sizes
    are seed-dependent, so with many requests per flush the rung's natural
    sub-batch size and bucket vary with flush *composition* — which would
    retrace on recompositions of already-seen work.  ``pad_to`` pads the
    sub-batch to the flush's slot count by replicating the last work item
    (batch-invariance makes replica mates inert — pinned in
    tests/test_batch_parity.py — and replicas reuse the last item's rung
    key, never touching an inactive item's chain), and ``bucket_hook(j,
    nb, mb) -> (nb, mb)`` lets the buffer pool pin per-(signature, rung)
    bucket high-water marks (oversized buckets are result-invariant).
    Together they make the compiled key a function of (flush signature,
    flush size) alone."""
    from repro.graphs.batch import bucket_size
    from repro.refine.drivers import make_refine_level_batched

    part = [s for s in st if j < s["n_levels"]]
    if not part:
        return
    lvl_graphs = []
    for s in part:
        if j == 0:
            s["lvl_g"] = s["coarsest"]
        else:
            fine, mapping = s["rungs"][j - 1]
            s["labels"] = s["labels"][mapping]  # project to finer level
            s["lvl_g"] = fine
        lvl_graphs.append(s["lvl_g"])
    n_pad = max(0, (pad_to or 0) - len(part))
    lvl_graphs += [lvl_graphs[-1]] * n_pad
    nb = bucket_size(max(g.n for g in lvl_graphs), minimum=8)
    mb = bucket_size(max(g.m for g in lvl_graphs), minimum=16)
    if bucket_hook is not None:
        nb, mb = bucket_hook(j, nb, mb)
    bg = _make_batched(lvl_graphs, nb, mb, batched)
    run = make_refine_level_batched(
        bg, k, rounds_taus=taus, patience=patience, max_inner=max_inner,
        gain=gain, variant=var.name, donate=donate)
    keys = []
    for s in part:
        s["key"], sub = jax.random.split(s["key"])
        keys.append(sub)
    keys += [keys[-1]] * n_pad
    lab_in = jnp.stack(
        [jnp.pad(s["labels"], (0, bg.n - s["lvl_g"].n)) for s in part]
        + [jnp.pad(part[-1]["labels"],
                   (0, bg.n - part[-1]["lvl_g"].n))] * n_pad)
    eps_j = [s["eps_l"][j] for s in part]
    eps_j += [eps_j[-1]] * n_pad
    out = run(lab_in, jnp.stack(keys), _lmax_batch(bg.nw, eps_j, k))
    for i, s in enumerate(part):
        s["labels"] = out[i, : s["lvl_g"].n]
        if trace_levels:
            s["trace"].append(level_trace_entry(
                s["lvl_g"].n, s["eps_l"][j],
                imbalance(s["lvl_g"], s["labels"], k)))


def finalize_result(s: dict, k: int, trace_levels: bool) -> PartitionResult:
    """Materialize one work item's result — the host sync point."""
    return PartitionResult(
        labels=s["labels"],
        cut=exact_edge_cut(s["g"], s["labels"]),
        imbalance=telemetry.host_read(
            "imbalance", imbalance(s["g"], s["labels"], k), float),
        levels=s["n_levels"],
        level_eps=s["eps_l"],
        level_trace=tuple(s["trace"]) if trace_levels else None,
    )


def partition_batch(
    graphs,
    k: int | None = UNSET,
    eps: float | None = UNSET,
    seed: int = 0,
    refiner: Refiner | None = UNSET,
    coarsen_until: int | None = UNSET,
    patience: int | None = UNSET,
    max_inner: int | None = UNSET,
    gain: str | None = UNSET,
    schedule: str | ToleranceSchedule | None = UNSET,
    eps_coarse: float | None = UNSET,
    trace_levels: bool = False,
    seeds=None,
    coalesce: bool = True,
    config: PartitionConfig | None = None,
) -> list[PartitionResult]:
    """Partition B graphs at once through the request-batched engine.

    Coarsening stays a per-graph host loop (data-dependent level sizes),
    but initial partitioning and every refinement level run as ONE compiled
    dispatch for the whole batch: per uncoarsening rung (aligned from each
    graph's coarsest level), the participating level graphs are padded to a
    shared power-of-two bucket (``repro.graphs.batch``) and refined by the
    ``vmap``-lifted level program (``drivers.make_refine_level_batched``),
    memoised on the bucket key — so a stream of requests whose levels land
    in the same buckets reuses compiled programs across calls.

    Identical in-flight requests — the same :class:`Graph` *object* with
    the same seed, the fan-out pattern batched serving exists for —
    **coalesce** into one engine slot whose result every alias shares
    (determinism makes the copies identical by construction, so computing
    them separately would be pure waste).  ``coalesce=False`` forces one
    slot per request; both paths return bit-identical results
    (tests/test_batch_parity.py).

    Every graph follows exactly the key chain and arithmetic of
    :func:`partition` with the same ``seed`` (override per graph via
    ``seeds``): the B=1 path is bit-identical to :func:`partition`, a
    graph's labels are independent of its bucket mates, of the batch order,
    and of the padding amount (tests/test_batch_parity.py).  Returns one
    :class:`PartitionResult` per graph, in input order.
    """
    from repro.core.refine import temperature_schedule

    cfg = resolve_config(config, where="partition_batch", k=k, eps=eps,
                         refiner=refiner, schedule=schedule,
                         eps_coarse=eps_coarse, gain=gain, patience=patience,
                         max_inner=max_inner, coarsen_until=coarsen_until)
    var, sched = cfg.variant(), cfg.tolerance_schedule()
    k, eps, gain = cfg.k, cfg.eps, cfg.gain
    patience, max_inner = cfg.patience, cfg.max_inner
    coarsen_until = cfg.coarsen_until
    if cfg.ckpt is not None:
        raise ValueError(
            "partition_batch: checkpointing (config.ckpt) is only supported "
            "by the solo V-cycle entry points partition/dpartition — batched "
            "slots share compiled programs and have no per-request rung "
            "state to snapshot")
    graphs = list(graphs)
    seeds = seed_list(graphs, seeds, seed)  # API-boundary check, even for []
    if not graphs:
        return []
    taus = temperature_schedule(var.rounds) if var.mode != "lp" else [0.0]

    # ---- request coalescing: identical requests share one engine slot ----
    slot_of, pairs = coalesce_slots(graphs, seeds, coalesce)

    # ---- per-graph host coarsening, replaying partition()'s key chain ----
    st = [exec_state(plan_request(g, s, k, sched, eps, coarsen_until))
          for g, s in pairs]

    # ---- batched initial partitioning: B × 4 restarts, one dispatch ----
    init_select(st, *init_dispatch(st, k, eps))

    # ---- rung-aligned batched refinement: one dispatch per rung ----
    for j in range(max(s["n_levels"] for s in st)):
        refine_rung(st, j, k, var, taus, patience, max_inner, gain,
                    trace_levels=trace_levels)

    res_u = [finalize_result(s, k, trace_levels) for s in st]
    # coalesced requests share the unique slot's (immutable) result
    return [res_u[j] for j in slot_of]
