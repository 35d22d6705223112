"""The unified Jet refinement engine (paper §2), written once over a comm
backend (``comm.py``) and a gain backend (``gain.py``).

This module holds the *only* copy of the arithmetic that used to live three
times in the repo (``core/jet.py`` + ``core/rebalance.py`` single-device,
``distributed/djet.py`` BSP, ``distributed/halo.py`` interface-only):

  * :func:`jet_move`        — candidate set M + afterburner + apply/lock;
  * :func:`afterburner_delta` — the assumed-state cut delta every variant's
    move filter is built from (``refine/variants.py``);
  * :func:`prob_pass`       — Alg. 1 probabilistic bucket rebalancing;
  * :func:`greedy_epoch`    — the dKaMinPar greedy rebalancer (two-stage
    top-k candidate gather + redundantly replayed global move sequence);
  * :func:`rebalance_loop`  — greedy epochs with the paper's <10 % progress
    escalation to the probabilistic pass;
  * :func:`jet_inner`       — (Jet → rebalance) until `patience`
    non-improvements of the best balanced partition;
  * :func:`refine_level`    — the whole d4xJet level: all temperature
    rounds fused into one ``lax.fori_loop`` so a level is ONE compiled
    device-resident program (see ``drivers.py``);
  * :func:`lp_round`        — the dLP baseline round.

Rebalance constants (paper Alg. 1) live here and nowhere else;
``core.rebalance`` re-exports them for backwards compatibility.

Determinism: every reduction is a fp32 sum of integers (exact), every
argmax/top-k tie-break is index-order on ids that are order-isomorphic to
global vertex ids in all backends, and all randomness is drawn in global
vertex space — so any gain × comm × P combination replays the same move
sequence from one seed (tests/test_refine_matrix.py).

Device scopes (``jax.named_scope``, op metadata only): ``gain`` on every
gain-backend call (:func:`gain_best`), ``move`` on the variant's move
function, ``afterburner``, ``rebalance`` and ``cut`` on the functions of
those names — so a device trace can split a level program's time by rung.
Every variant, comm backend and the batched path carry them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.refine.comm import EdgeView

NEG = -jnp.inf

# ---- paper Alg. 1 rebalance constants (single source of truth) ------------
ALPHA = 1.1          # paper §2: "we use α = 1.1"
N_BUCKETS = 96       # static bucket count; r_v ≈ −1e4 lands in bucket ~97 → clip
GREEDY_NCAND = 128   # "a few vertices per overloaded block in every epoch"


def _relative_gain(gain: jax.Array, cv: jax.Array) -> jax.Array:
    """r_v = g_v·c(v) if g_v > 0 else g_v/c(v)  (paper Alg. 1 line 4)."""
    cv = jnp.maximum(cv, 1e-9)
    return jnp.where(gain > 0, gain * cv, gain / cv)


def _bucket_index(r: jax.Array) -> jax.Array:
    """Exponentially spaced bucket index (paper Alg. 1 line 5)."""
    neg = 1.0 + jnp.ceil(jnp.log1p(jnp.maximum(-r, 0.0)) / jnp.log(ALPHA))
    j = jnp.where(r >= 0, 0.0, neg)
    return jnp.clip(j, 0, N_BUCKETS - 1).astype(jnp.int32)


# --------------------------------------------------------------------------
# shared per-round helpers
# --------------------------------------------------------------------------

def _head_labels(cm, ev: EdgeView, labels):
    """Per-edge labels of heads — the ghost/halo label update + lookup."""
    return cm.lookup(ev, cm.exchange(labels), labels)


def block_weights(cm, ev: EdgeView, labels, k: int):
    return cm.psum(jax.ops.segment_sum(ev.nw, labels, num_segments=k))


def overload_of(cm, ev: EdgeView, labels, k: int, lmax):
    bw = block_weights(cm, ev, labels, k)
    return jnp.sum(jnp.maximum(bw - lmax, 0.0))


@jax.named_scope("cut")
def cut_of(cm, ev: EdgeView, labels):
    lv = _head_labels(cm, ev, labels)
    w = jnp.where(ev.live & (labels[ev.src] != lv), ev.ew, 0.0)
    return cm.psum(jnp.sum(w)) * 0.5


def gain_best(gb, ev: EdgeView, lv_e, labels, capacity):
    """``gb.best`` — (own, gain, target) per vertex — under the scope
    ``gain``; every caller of a gain backend goes through here."""
    with jax.named_scope("gain"):
        return gb.best(ev, lv_e, labels, capacity)


# --------------------------------------------------------------------------
# Jet round: candidate set + afterburner (paper §2 "Jet Refinement")
# --------------------------------------------------------------------------

@jax.named_scope("afterburner")
def afterburner_delta(cm, lay, labels, lv_e, gain, target, cand,
                      order: str = "gain"):
    """Assumed-state cut delta of every candidate move: exchange
    (g(v), target, ∈M); u precedes v iff (g(u), −u) > (g(v), −v) in the
    virtual order, and v re-evaluates its move assuming every preceding
    candidate neighbour has already moved.  The single copy of the
    afterburner arithmetic — every variant's move filter
    (``refine/variants.py``) is a predicate over this delta.

    ``order`` picks the virtual order: ``"gain"`` (the Jet paper's
    (gain desc, id asc) order) or ``"vertex"`` (plain global-vertex-id
    order, the Jet_v flavour — the gain exchange is skipped).  The
    per-round no-cut-increase guarantee is specific to the gain order
    (the proof needs predecessors to have no smaller gain); the vertex
    order trades it for one fewer exchange and relies on the level
    driver's best-balanced tracking instead.  Both orders are
    order-isomorphic to global vertex ids in every backend, so the
    determinism contract holds for either.

    ``lay`` is the edge layout the sums run in (``gb.layout(ev)``): the
    m-slot :class:`EdgeView`, or the gain kernel's (D, N) ``DenseView``,
    where a tail value is a broadcast and a row sum a column sum.
    ``lv_e`` is the per-slot head labels the gain backend read."""
    if order == "vertex":
        tu, cu, _ = lay.head_moves(cm, target, cand)
        precede = cu & (lay.head_tid < lay.tails(lay.my_tid))
    elif order == "gain":
        gmask = jnp.where(cand, gain, NEG)
        tu, cu, gu = lay.head_moves(cm, target, cand, gmask)
        gv = lay.tails(gain)
        precede = cu & ((gu > gv)
                        | ((gu == gv)
                           & (lay.head_tid < lay.tails(lay.my_tid))))
    else:
        raise ValueError(f"afterburner order must be 'gain' or 'vertex', "
                         f"got {order!r}")
    assumed = jnp.where(precede, tu, lay.relayout(lv_e))

    w = jnp.where(lay.live, lay.ew, 0.0)
    tv = lay.tails(target)
    lown = lay.tails(labels)
    delta_e = w * ((assumed == tv).astype(w.dtype)
                   - (assumed == lown).astype(w.dtype))
    return lay.rowsum(delta_e)


def candidate_set(ev: EdgeView, labels, own, gain, target, tau, locked=None):
    """Candidate set M — the single copy of the admission rule: negative
    gains admitted up to −⌊τ·conn_own⌋, finite-gain real moves of owned
    slots only, optionally excluding ``locked`` vertices.  Variants AND
    extra predicates onto the returned mask."""
    threshold = -jnp.floor(tau * own)
    cand = (gain >= threshold) & (target != labels)
    cand &= jnp.isfinite(gain) & ev.owned
    if locked is not None:
        cand &= ~locked
    return cand


def jet_move(cm, gb, ev: EdgeView, labels, locked, tau, k: int):
    """One Jet round; returns (new_labels, moved mask)."""
    lv_e = _head_labels(cm, ev, labels)
    own, gain, target = gain_best(gb, ev, lv_e, labels, None)
    cand = candidate_set(ev, labels, own, gain, target, tau, locked)
    delta = afterburner_delta(cm, gb.layout(ev), labels, lv_e, gain, target,
                              cand)
    move = cand & (delta >= 0.0)
    return jnp.where(move, target, labels), move


# --------------------------------------------------------------------------
# Alg. 1 — probabilistic bucket rebalancing
# --------------------------------------------------------------------------

def prob_pass(cm, gb, ev: EdgeView, labels, key, lmax, k: int):
    bw = block_weights(cm, ev, labels, k)
    overloaded = bw > lmax
    capacity = jnp.where(~overloaded, lmax - bw, NEG)

    lv_e = _head_labels(cm, ev, labels)
    _, gain, target = gain_best(gb, ev, lv_e, labels, capacity)

    mover = overloaded[labels] & jnp.isfinite(gain) & ev.owned & (ev.nw > 0)
    bucket = _bucket_index(_relative_gain(gain, ev.nw))

    # per-(overloaded block, bucket) weights c(B_o^i) — Alg. 1 line 8
    B = cm.psum(jax.ops.segment_sum(
        jnp.where(mover, ev.nw, 0.0), labels * N_BUCKETS + bucket,
        num_segments=k * N_BUCKETS,
    )).reshape(k, N_BUCKETS)

    prefix = jnp.cumsum(B, axis=1)
    excess = jnp.maximum(bw - lmax, 0.0)
    covered = prefix >= excess[:, None]
    cutoff = jnp.where(jnp.any(covered, axis=1),
                       jnp.argmax(covered, axis=1) + 1, N_BUCKETS)
    cutoff = jnp.where(excess > 0, cutoff, 0)

    move_cand = mover & (bucket < cutoff[labels])
    W = cm.psum(jax.ops.segment_sum(
        jnp.where(move_cand, ev.nw, 0.0), target, num_segments=k))
    room = jnp.maximum(lmax - bw, 0.0)
    p = jnp.where(W > 0, jnp.minimum(room / jnp.maximum(W, 1e-9), 1.0), 0.0)

    accept = move_cand & (cm.uniform(key, ev) < p[target])
    return jnp.where(accept, target, labels)


# --------------------------------------------------------------------------
# Greedy rebalancer (dKaMinPar Ref. [9]) — two-stage top-k + replay
# --------------------------------------------------------------------------

_IMAX = jnp.iinfo(jnp.int32).max


def _lex_best(a, b):
    """Reduction of (score, tid, index) triples to the lexicographic best:
    score desc, then tid asc, then index asc — associative and commutative
    on NaN-free scores, so any reduction tree gives the same triple."""
    sa, ta, ia = a
    sb, tb, ib = b
    take_b = (sb > sa) | ((sb == sa) & ((tb < ta) | ((tb == ta) & (ib < ia))))
    return (jnp.where(take_b, sb, sa), jnp.where(take_b, tb, ta),
            jnp.where(take_b, ib, ia))


def top_candidates(score, tid, n_sel: int):
    """Slot indices of the ``n_sel`` best slots in (score desc, tid asc,
    slot asc) order — ``jnp.lexsort((tid, -score))[:n_sel]`` for NaN-free
    scores, computed as ``n_sel`` lexicographic max-reductions instead of a
    sort of all n_local slots: a TPU sort of 2^20 elements takes ~40 s to
    compile per level program, a reduction well under one."""
    idx = jnp.arange(score.shape[0], dtype=jnp.int32)

    def body(j, carry):
        s, t, ix, sel = carry
        _, _, i = jax.lax.reduce((s, t, ix), (NEG, _IMAX, _IMAX), _lex_best,
                                 (0,))
        # a taken slot becomes (-inf, IMAX, IMAX): it loses every tie
        return (s.at[i].set(NEG), t.at[i].set(_IMAX), ix.at[i].set(_IMAX),
                sel.at[j].set(i))

    init = (score.astype(jnp.float32), tid.astype(jnp.int32), idx,
            jnp.zeros((n_sel,), jnp.int32))
    return jax.lax.fori_loop(0, n_sel, body, init)[3]


def greedy_epoch(cm, gb, ev: EdgeView, labels, lmax, k: int,
                 ncand: int = GREEDY_NCAND):
    """One centrally coordinated epoch.

    Stage 1: each PE top-k's its own candidates by r_v (the global top-ncand
    is contained in the union of per-PE top-ncands).  Stage 2: one small
    ``gather`` of the per-PE candidate records, then every PE redundantly
    replays the same deterministic global move sequence with live weight
    accounting — O(P·ncand) wire bytes instead of the full label gather.
    """
    bw = block_weights(cm, ev, labels, k)
    overloaded = bw > lmax
    capacity = jnp.where(~overloaded, lmax - bw, NEG)

    lv_e = _head_labels(cm, ev, labels)
    _, gain, target = gain_best(gb, ev, lv_e, labels, capacity)

    mover = overloaded[labels] & jnp.isfinite(gain) & ev.owned
    score = jnp.where(mover, _relative_gain(gain, ev.nw), NEG)

    # selection order is (score desc, tie-break id asc) — EXPLICITLY, not by
    # slot position: halo slots are permuted interface-first, so positional
    # top_k stability would break the cross-backend determinism contract
    nc_loc = min(ncand, ev.n_local)
    idx = top_candidates(score, ev.my_tid, nc_loc)

    rec_s = cm.gather(score[idx])
    rec_tid = cm.gather(ev.my_tid[idx])
    rec_tgt = cm.gather(target[idx])
    rec_w = cm.gather(ev.nw[idx])
    rec_lab = cm.gather(labels[idx])

    n_rec = min(ncand, rec_s.shape[0])
    ord2 = jnp.lexsort((rec_tid, -rec_s))[:n_rec]
    s2 = rec_s[ord2]
    tid2, tgt2 = rec_tid[ord2], rec_tgt[ord2]
    w2, lab2 = rec_w[ord2], rec_lab[ord2]

    def body(i, carry):
        moved, bw = carry
        ok = (
            jnp.isfinite(s2[i])
            & (bw[lab2[i]] > lmax)
            & (bw[tgt2[i]] + w2[i] <= lmax)
            & (tgt2[i] != lab2[i])
        )
        moved = moved.at[i].set(ok)
        dw = jnp.where(ok, w2[i], 0.0)
        bw = bw.at[lab2[i]].add(-dw).at[tgt2[i]].add(dw)
        return moved, bw

    moved, _ = jax.lax.fori_loop(
        0, n_rec, body, (jnp.zeros((n_rec,), bool), bw))
    return cm.apply_moves(ev, labels, tid2, tgt2, moved)


# --------------------------------------------------------------------------
# Rebalance driver: greedy epochs + <10 % progress escalation (paper §2)
# --------------------------------------------------------------------------

@jax.named_scope("rebalance")
def rebalance_loop(cm, gb, ev: EdgeView, labels, key, lmax, k: int,
                   max_epochs: int = 32, ncand: int = GREEDY_NCAND):
    """Returns (labels, overload, epochs, prob_passes)."""

    def cond(state):
        _, _, ov, ep, _ = state
        return (ov > 0) & (ep < max_epochs)

    def body(state):
        labels, key, ov, ep, pp = state
        labels = greedy_epoch(cm, gb, ev, labels, lmax, k, ncand)
        new_ov = overload_of(cm, ev, labels, k, lmax)
        slow = new_ov > 0.9 * ov  # <10 % progress → escalate to Alg. 1
        key, sub = jax.random.split(key)
        labels = jax.lax.cond(
            slow,
            lambda l: prob_pass(cm, gb, ev, l, sub, lmax, k),
            lambda l: l,
            labels,
        )
        new_ov = jax.lax.cond(
            slow, lambda l: overload_of(cm, ev, l, k, lmax),
            lambda _: new_ov, labels)
        return labels, key, new_ov, ep + 1, pp + slow.astype(jnp.int32)

    ov0 = overload_of(cm, ev, labels, k, lmax)
    labels, _, ov, ep, pp = jax.lax.while_loop(
        cond, body, (labels, key, ov0, jnp.int32(0), jnp.int32(0)))
    return labels, ov, ep, pp


# --------------------------------------------------------------------------
# d4xJet integration: inner (Jet → rebalance) loop + fused temperature loop
# --------------------------------------------------------------------------

def jet_inner(cm, gb, ev: EdgeView, labels, tau, lmax, key, k: int,
              patience: int, max_inner: int, move_fn=jet_move):
    """One temperature round: repeat (move_fn → rebalance_loop) until
    `patience` consecutive failures to improve the best balanced cut.

    ``move_fn`` is the variant's move-generation function (the
    ``refine/variants.py`` contract; default: the Jet rule)."""

    def cond(s):
        _, _, _, _, since, it, _ = s
        return (since < patience) & (it < max_inner)

    def body(s):
        labels, locked, best_labels, best_cut, since, it, key = s
        key, k_reb = jax.random.split(key)
        with jax.named_scope("move"):
            labels, moved = move_fn(cm, gb, ev, labels, locked, tau, k)
        labels, ov, _, _ = rebalance_loop(cm, gb, ev, labels, k_reb, lmax, k)
        cut = cut_of(cm, ev, labels)
        improved = (ov <= 0) & (cut < best_cut)
        best_labels = jnp.where(improved, labels, best_labels)
        best_cut = jnp.where(improved, cut, best_cut)
        since = jnp.where(improved, 0, since + 1)
        return labels, moved, best_labels, best_cut, since, it + 1, key

    cut0 = cut_of(cm, ev, labels)
    ov0 = overload_of(cm, ev, labels, k, lmax)
    best_cut0 = jnp.where(ov0 <= 0, cut0, jnp.inf)
    init = (labels, jnp.zeros(ev.n_local, bool), labels, best_cut0,
            jnp.int32(0), jnp.int32(0), key)
    labels, _, best_labels, best_cut, _, _, _ = jax.lax.while_loop(
        cond, body, init)
    # if no balanced state was ever seen, fall back to the last labels
    return jnp.where(jnp.isfinite(best_cut), best_labels, labels)


def refine_level(cm, gb, ev: EdgeView, labels, key, lmax, taus, k: int,
                 patience: int, max_inner: int, move_fn=jet_move):
    """Whole-level d4xJet: the temperature rounds are a ``fori_loop`` over
    the (traced) ``taus`` vector, so the level is one compiled program —
    O(1) dispatches instead of O(rounds · inner · epochs).  ``move_fn``
    selects the refinement variant's move-generation rule."""

    def round_body(i, carry):
        labels, key = carry
        key, sub = jax.random.split(key)
        labels = jet_inner(cm, gb, ev, labels, taus[i], lmax, sub, k,
                           patience, max_inner, move_fn=move_fn)
        return labels, key

    labels, _ = jax.lax.fori_loop(0, taus.shape[0], round_body, (labels, key))
    return labels


# --------------------------------------------------------------------------
# dLP baseline round (size-constrained label propagation)
# --------------------------------------------------------------------------

def lp_round(cm, gb, ev: EdgeView, labels, key, lmax, k: int):
    bw = block_weights(cm, ev, labels, k)
    capacity = lmax - bw
    lv_e = _head_labels(cm, ev, labels)
    _, gain, target = gain_best(gb, ev, lv_e, labels, capacity)
    want = (gain > 0) & jnp.isfinite(gain) & ev.owned

    w_in = cm.psum(jax.ops.segment_sum(
        jnp.where(want, ev.nw, 0.0), target, num_segments=k))
    p = jnp.where(w_in > 0,
                  jnp.clip(capacity / jnp.maximum(w_in, 1e-9), 0.0, 1.0), 1.0)
    accept = want & (cm.uniform(key, ev) < p[target])
    return jnp.where(accept, target, labels)


def lp_level(cm, gb, ev: EdgeView, labels, key, lmax, k: int,
             lp_rounds: int = 8, max_epochs: int = 32):
    """Fused dLP level: ``lp_rounds`` LP rounds + the rebalance finisher,
    one compiled program."""

    def body(i, carry):
        labels, key = carry
        key, sub = jax.random.split(key)
        labels = lp_round(cm, gb, ev, labels, sub, lmax, k)
        return labels, key

    labels, key = jax.lax.fori_loop(0, lp_rounds, body, (labels, key))
    key, sub = jax.random.split(key)
    labels, _, _, _ = rebalance_loop(cm, gb, ev, labels, sub, lmax, k,
                                     max_epochs)
    return labels
