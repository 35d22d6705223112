"""Gain backends of the unified refinement engine (DESIGN.md §5).

A gain backend answers one question per round, for every owned vertex v:

    own(v)    = conn(v, V_own)
    gain(v)   = max_{j eligible} conn(v, V_j) − own(v)
    target(v) = argmax_{j eligible} conn(v, V_j)

with eligibility j ≠ own(v) ∧ capacity[j] ≥ c(v) (``capacity=None`` means
unconstrained Jet move generation).  Two implementations:

  * :class:`JnpGain`    — the streaming ``segment_sum`` formulation (one
    (n_local·k,) scatter-add per round); works at any degree / k.
  * :class:`PallasGain` — the VMEM scoreboard kernel
    (``kernels/gain/kernel.py``): a dense (TILE_N, K) tile accumulated
    DEG_CHUNK neighbours at a time.  Needs the padded adjacency, built once
    per level from the edge view, and is subject to the DESIGN.md §5 VMEM
    envelope — :func:`resolve_gain` applies the max_deg/K fallback rule
    automatically.

Both backends compute bit-identical results on integer-weight graphs (fp32
sums of integers < 2²⁴ are exact; argmax tie-breaks are index-order in
both), which is what lets the determinism contract span the gain axis of
the backend matrix.

Each backend also names the edge layout the afterburner runs in beside it
(``layout``; :data:`EDGE_LAYOUT` by backend name): the Pallas backend's
padded adjacency is sized by the level's max_deg and k, so its (D, N)
:class:`~repro.refine.comm.DenseView` serves the afterburner too; past
that envelope a (D, N) table would be far larger than m, and the jnp
backend keeps the m-slot :class:`~repro.refine.comm.EdgeView`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import default_interpret
from repro.kernels.gain.kernel import LANE, gain_scoreboard_pallas
from repro.kernels.gain.kernel import round_up as _round_up
from repro.refine.comm import dense_view

PALLAS_MAX_DEG = 2048  # DESIGN.md §5: VMEM envelope of the scoreboard kernel
PALLAS_MAX_K = 1024

# the kind of the layout each backend's ``layout`` returns
EDGE_LAYOUT = {"jnp": "slot", "pallas": "dense"}


def resolve_gain(kind: str, k: int, max_deg: int | None) -> str:
    """Apply the DESIGN.md §5 fallback rule: the Pallas scoreboard serves
    max_deg ≤ 2048 and k ≤ 1024; anything larger streams through HBM via
    the jnp segment-sum path.  ``kind="auto"`` means "pallas if it fits"."""
    if kind == "auto":
        kind = "pallas"
    if kind not in ("jnp", "pallas"):
        raise ValueError(f"gain backend must be 'jnp', 'pallas' or 'auto', got {kind!r}")
    if kind == "pallas" and (
        max_deg is None or max_deg > PALLAS_MAX_DEG or k > PALLAS_MAX_K
    ):
        return "jnp"
    return kind


def masked_best(conn, labels, nw, capacity, k: int):
    """(own, gain, target) from a dense (n, k) connectivity matrix — the
    shared move-selection rule (index-order argmax tie-break; gain = −inf
    and target = own block when no block is eligible)."""
    own = jnp.take_along_axis(conn, labels[:, None], axis=1)[:, 0]
    blk = jnp.arange(k, dtype=jnp.int32)
    eligible = blk[None, :] != labels[:, None]
    if capacity is not None:
        eligible &= capacity[None, :] >= nw[:, None]
    masked = jnp.where(eligible, conn, -jnp.inf)
    tgt = jnp.argmax(masked, axis=1).astype(jnp.int32)
    best = jnp.max(masked, axis=1)
    gain = jnp.where(jnp.isfinite(best), best - own, -jnp.inf)
    tgt = jnp.where(jnp.isfinite(best), tgt, labels)
    return own, gain, tgt


class JnpGain:
    """Segment-sum gain backend — the HBM-streaming reference path."""

    kind = "jnp"

    def __init__(self, k: int):
        self.k = k

    def layout(self, ev):
        """The afterburner's edge layout: the m slots of ``ev`` itself."""
        return ev

    def best(self, ev, lv_e, labels, capacity):
        n_loc = ev.n_local
        w = jnp.where(ev.live, ev.ew, 0.0)
        key = ev.src * self.k + jnp.where(ev.live, lv_e, 0)
        conn = jax.ops.segment_sum(
            w, key, num_segments=n_loc * self.k
        ).reshape(n_loc, self.k)
        return masked_best(conn, labels, ev.nw, capacity, self.k)


class PallasGain:
    """Scoreboard-kernel gain backend.

    Construction (once per level, loop-invariant inside the fused level
    program) builds the neighbour-major padded adjacency in *edge-slot*
    coordinates: ``eslot[r, v]`` is the edge index of v's r-th neighbour
    (m = padding).
    Per round the head labels are produced by the comm backend's per-edge
    lookup and gathered through ``eslot`` — so one padded adjacency serves
    every round and every comm backend.
    """

    kind = "pallas"

    def __init__(self, ev, k: int, max_deg: int, tile_n: int | None = None,
                 deg_chunk: int | None = None, interpret: bool | None = None):
        self.k = k
        self.interpret = default_interpret(interpret)
        n_loc = ev.n_local
        # tile parameters left None resolve from the committed autotune
        # table (kernels/tune.py) — a trace-time, per-process-deterministic
        # lookup, so the drivers' lru_cache keys need not carry tile config
        # and bucket-cache keys stay stable.  Tiles never change results
        # (padding rows/columns are inert), only speed.
        if tile_n is None or deg_chunk is None:
            from repro.kernels.tune import backend_name, lookup

            cfg = lookup("gain", n=n_loc, d=max(int(max_deg), 1), k=k,
                         backend=backend_name(self.interpret))
            tile_n = cfg["tile_n"] if tile_n is None else tile_n
            deg_chunk = cfg["deg_chunk"] if deg_chunk is None else deg_chunk
        self.tile_n = tile_n
        self.deg_chunk = deg_chunk
        m = ev.src.shape[0]
        d = _round_up(max(int(max_deg), 1), deg_chunk)
        n_pad = _round_up(max(n_loc, 1), tile_n)

        # rank of each live edge within its row.  Every layout stores a
        # row's live edges as one run of the live slots (CSR order, row ids
        # possibly relabelled — the halo layout), so the rank is the count
        # of live slots since the row's first one: two cumulative counts
        # and a segment-min, no sort (a TPU sort of m slots costs tens of
        # seconds of compile per level program)
        live_i = ev.live.astype(jnp.int32)
        before = jnp.cumsum(live_i) - live_i      # live slots before e
        row = jnp.where(ev.live, ev.src, n_loc).astype(jnp.int32)
        first = jax.ops.segment_min(before, row, num_segments=n_loc + 1)
        rank = before - first[row]

        ok = ev.live & (rank < d)
        rows = jnp.where(ok, ev.src, n_pad)     # pads routed out of bounds
        cols = jnp.where(ok, rank, 0)
        slots = jnp.arange(m, dtype=jnp.int32)
        self.eslot = jnp.full((d, n_pad), m, jnp.int32).at[cols, rows].set(
            jnp.where(ok, slots, m), mode="drop"
        )
        self.nbr_w = jnp.zeros((d, n_pad), jnp.float32).at[cols, rows].set(
            jnp.where(ok, ev.ew, 0.0), mode="drop"
        )
        self.dense = dense_view(ev, self.eslot, self.nbr_w)
        self.n_loc = n_loc
        self.n_pad = n_pad

    def layout(self, ev):
        """The afterburner's edge layout: the level's (D, N)
        :class:`~repro.refine.comm.DenseView`."""
        return self.dense

    def best(self, ev, lv_e, labels, capacity):
        k_pad = _round_up(self.k, LANE)
        cap_k = (
            jnp.full((self.k,), jnp.inf, jnp.float32)
            if capacity is None else capacity
        )
        cap = jnp.full((k_pad,), -jnp.inf, jnp.float32).at[: self.k].set(cap_k)
        # eslot names live slots only, so dead slots' lv_e is never read;
        # the afterburner relayouts lv_e the same way, and XLA computes
        # the one table for both
        nbr_lab = self.dense.relayout(lv_e)
        pad = self.n_pad - self.n_loc
        lab_p = jnp.pad(labels, (0, pad))
        nw_p = jnp.pad(ev.nw, (0, pad))
        own, gain, tgt = gain_scoreboard_pallas(
            nbr_lab, self.nbr_w, lab_p, nw_p, cap,
            tile_n=self.tile_n, deg_chunk=self.deg_chunk,
            interpret=self.interpret,
        )
        return own[: self.n_loc], gain[: self.n_loc], tgt[: self.n_loc]


def make_gain(kind: str, ev, k: int, max_deg: int | None = None,
              interpret: bool | None = None, tile_n: int | None = None,
              deg_chunk: int | None = None):
    """Instantiate the gain backend for one level, applying the fallback
    rule.  ``max_deg`` is the true maximum degree of the level (a static,
    setup-time scalar — it sizes the padded adjacency).  ``tile_n``/
    ``deg_chunk`` left ``None`` (the production setting) resolve from the
    committed autotune table; explicit values always win (the tile-sweep
    parity tests' hook)."""
    kind = resolve_gain(kind, k, max_deg)
    if kind == "pallas":
        return PallasGain(ev, k, max_deg, tile_n=tile_n, deg_chunk=deg_chunk,
                          interpret=interpret)
    return JnpGain(k)
