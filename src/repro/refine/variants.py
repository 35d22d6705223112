"""The refinement-variant registry (DESIGN.md §2 "Refinement variants").

The paper's core contribution is an *unconstrained* local search whose
quality hinges on the move-generation rule.  With the unified engine, a new
rule is one function over the existing gain × comm backends — no new comm
code.  This module is the single registry of those rules; ``partition`` /
``dpartition`` resolve their ``refiner=`` argument here, and the fused level
drivers (``drivers.py``) look the move function up by variant name (a
static, hashable cache key).

Move-generation contract — a variant's ``move`` function has the signature

    move(cm, gb, ev, labels, locked, tau, k) -> (new_labels, moved_mask)

with ``cm`` a comm backend, ``gb`` a gain backend, ``ev`` the level's
:class:`~repro.refine.comm.EdgeView`, ``locked`` the engine's
moved-last-iteration mask, and ``tau`` the current temperature.  A variant
MUST (a) only move ``ev.owned`` slots, (b) keep every reduction an exact
fp32 sum of integers and every tie-break index-order on ``my_tid`` /
``head_tid`` (order-isomorphic to global vertex ids in every backend), and
(c) draw any randomness through ``cm.uniform`` — then the determinism
contract extends to it for free: bit-identical partitions across
{gain} × {comm} × P from one seed (tests/test_variants.py).

Registered variants (Gottesbüren et al., "Parallel Unconstrained Local
Search for Partitioning Irregular Graphs" — the JetLP family):

  * ``jet``   — the paper's Jet rule (d4xJet default): negative gains
    admitted up to −⌊τ·conn_own⌋, movers locked for the next iteration,
    afterburner keeps moves with assumed-state delta ≥ 0.
  * ``jetlp`` — LP-style unconstrained moves under the same JetLP
    negative-gain tolerance schedule: no lock (every vertex is reconsidered
    every iteration, label-propagation semantics); oscillation is damped by
    the afterburner instead, which admits a *negative*-gain candidate only
    on strictly positive assumed-state delta.
  * ``jet_h`` — heavy-vertex-deferred Jet: vertices heavier than the
    level's mean owned vertex weight enter M only on strictly positive
    gain, so the rebalancer never has to haul a wandering heavy vertex
    back across blocks.
  * ``jet_v`` — vertex-ordered Jet: the afterburner's virtual order is
    plain global-vertex-id order instead of (gain desc, id asc), which
    drops the per-round gain exchange (one fewer ``exchange`` per Jet
    iteration) at the cost of the gain order's per-round
    no-cut-increase guarantee (the level driver's best-balanced
    tracking restores monotonicity at level granularity).
  * ``lp``    — the size-constrained label-propagation baseline
    (``engine.lp_level``; no temperature loop).

Aliases keep the paper-configuration names working: ``d4xjet`` → ``jet``
(4 temperature rounds), ``djet`` → ``jet`` with 1 round, ``djet_v`` →
``jet_v`` with 1 round, ``dlp`` → ``lp``.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax.numpy as jnp

from repro.refine import engine
from repro.refine.comm import EdgeView


class Variant(NamedTuple):
    """One registered refinement variant.

    ``mode`` picks the fused level program: ``"jet"`` (temperature loop ×
    inner (move → rebalance → patience) loop, ``engine.refine_level``) or
    ``"lp"`` (LP rounds + rebalance finisher, ``engine.lp_level``).
    ``move`` is the jet-mode move-generation function (None for lp-mode);
    ``rounds`` the default temperature-round count of the τ schedule.
    """

    name: str
    mode: str
    move: Callable | None
    rounds: int


# --------------------------------------------------------------------------
# move-generation rules (each one is the ~50-line cost of a new variant)
# --------------------------------------------------------------------------

def jetlp_move(cm, gb, ev: EdgeView, labels, locked, tau, k: int):
    """JetLP: LP-style unconstrained moves, ``locked`` ignored.  The
    negative-gain tolerance schedule is the same τ ramp as Jet; in place of
    Jet's lock, negative-gain candidates survive the afterburner only on
    strictly positive assumed-state delta (zero-delta shuffles of admitted
    bad moves are what oscillates without a lock)."""
    lv_e = engine._head_labels(cm, ev, labels)
    own, gain, target = engine.gain_best(gb, ev, lv_e, labels, None)
    cand = engine.candidate_set(ev, labels, own, gain, target, tau)
    delta = engine.afterburner_delta(cm, gb.layout(ev), labels, lv_e, gain,
                                     target, cand)
    move = cand & jnp.where(gain < 0, delta > 0.0, delta >= 0.0)
    return jnp.where(move, target, labels), move


def jet_h_move(cm, gb, ev: EdgeView, labels, locked, tau, k: int):
    """Heavy-vertex-deferred Jet: the Jet rule, except vertices heavier
    than the level's mean owned vertex weight are admitted to M only on
    strictly positive gain.  The mean is an exact psum'd fp32
    integer-sum ratio, so the heavy mask is identical in every backend."""
    lv_e = engine._head_labels(cm, ev, labels)
    own, gain, target = engine.gain_best(gb, ev, lv_e, labels, None)

    # level-invariant, recomputed per iteration: two *scalar* psums, noise
    # next to the O(n) label exchange every iteration already performs
    w_tot = cm.psum(jnp.sum(jnp.where(ev.owned, ev.nw, 0.0)))
    n_tot = cm.psum(jnp.sum(ev.owned.astype(jnp.float32)))
    heavy = ev.nw > w_tot / jnp.maximum(n_tot, 1.0)

    cand = engine.candidate_set(ev, labels, own, gain, target, tau, locked)
    cand &= (~heavy) | (gain > 0.0)

    delta = engine.afterburner_delta(cm, gb.layout(ev), labels, lv_e, gain,
                                     target, cand)
    move = cand & (delta >= 0.0)
    return jnp.where(move, target, labels), move


def jet_v_move(cm, gb, ev: EdgeView, labels, locked, tau, k: int):
    """Vertex-ordered Jet: identical to the Jet rule except the
    afterburner's virtual order is plain global-vertex-id order
    (``order="vertex"``), so the per-round gain exchange disappears.  The
    gain order's per-round no-cut-increase guarantee does NOT transfer
    (tests/test_schedule_property.py pins the distinction) — the level
    stays monotone from a balanced start through ``jet_inner``'s
    best-balanced tracking instead.  Vertex-id order is order-isomorphic
    to global ids in every backend, so the determinism contract extends
    for free."""
    lv_e = engine._head_labels(cm, ev, labels)
    own, gain, target = engine.gain_best(gb, ev, lv_e, labels, None)
    cand = engine.candidate_set(ev, labels, own, gain, target, tau, locked)
    delta = engine.afterburner_delta(cm, gb.layout(ev), labels, lv_e, gain,
                                     target, cand, order="vertex")
    move = cand & (delta >= 0.0)
    return jnp.where(move, target, labels), move


# --------------------------------------------------------------------------
# registry
# --------------------------------------------------------------------------

_REGISTRY: dict[str, Variant] = {}


def register(variant: Variant) -> Variant:
    """Register a variant (importable hook for out-of-tree rules)."""
    if variant.name in _REGISTRY:
        raise ValueError(f"variant {variant.name!r} already registered")
    if variant.mode not in ("jet", "lp"):
        raise ValueError(f"variant mode must be 'jet' or 'lp', got {variant.mode!r}")
    if variant.mode == "jet" and variant.move is None:
        raise ValueError(f"jet-mode variant {variant.name!r} needs a move function")
    _REGISTRY[variant.name] = variant
    return variant


JET = register(Variant("jet", "jet", engine.jet_move, rounds=4))
JETLP = register(Variant("jetlp", "jet", jetlp_move, rounds=4))
JET_H = register(Variant("jet_h", "jet", jet_h_move, rounds=4))
JET_V = register(Variant("jet_v", "jet", jet_v_move, rounds=4))
LP = register(Variant("lp", "lp", None, rounds=1))

# paper-configuration aliases (not separate registry entries: `djet` is the
# jet rule with a 1-round — i.e. cold, τ = τ1 — schedule).  The resolved
# Variant keeps its canonical ``name`` so the level drivers reuse the same
# compiled programs for alias and canonical spellings.
ALIASES: dict[str, Variant] = {
    "d4xjet": JET,
    "djet": JET._replace(rounds=1),
    "djet_v": JET_V._replace(rounds=1),
    "dlp": LP,
}


def registered_variants() -> tuple[str, ...]:
    """Canonical variant names, sorted (aliases not included)."""
    return tuple(sorted(_REGISTRY))


def resolve_variant(name: str) -> Variant:
    """Resolve a ``refiner=`` name to its :class:`Variant`, accepting the
    paper-configuration aliases; raises ``ValueError`` listing what IS
    registered — called eagerly by ``partition``/``dpartition`` so a typo
    fails at the API boundary, not deep in driver selection."""
    if name in _REGISTRY:
        return _REGISTRY[name]
    if name in ALIASES:
        return ALIASES[name]
    raise ValueError(
        f"unknown refiner {name!r}: registered variants are "
        f"{list(registered_variants())} "
        f"(aliases: {sorted(ALIASES)})")
