"""Pallas TPU kernel: halo move application.

The halo refinement hot path ends every greedy-rebalance epoch with
"apply the replayed global move list to my owned slots".  The XLA
rendering (``HaloComm.apply_moves``) is a chain — range test →
``inv_perm`` gather → scatter — each a separate HBM round trip.

``halo_apply_pallas`` replaces the chain with one VMEM-resident pass, a
dense gid-compare: labels and per-slot global ids stream through VMEM in
(TILE_N, 1) tiles while the whole move list (ncand ≤ a few thousand ids)
stays resident as (1, C) lane vectors; CAND_CHUNK candidates are compared
per step.  Slot i takes ``tgts[j]`` iff ``moved[j] ∧ tids[j] == gid[i]``.
This is *equivalent* to the range-test + inverse-permutation formulation
because (a) a non-owned slot carries ``gid = PAD`` which matches no real
move id, and (b) the engine's move list contains each global id at most
once (candidates are per-owned-vertex and every vertex is owned by exactly
one PE), so the max-select over matches returns the unique target.

The halo↔block label *relayout* is not a kernel: it is a whole-shard
permutation gather, and Mosaic gathers only within one (8, 128) vreg
("Multiple source vregs along gather dimension"), so the level program
renders it with XLA's gather on every backend (DESIGN.md §5).

The output is int32 — the kernel moves labels, never weights — so
"bit-identical" here is exact integer equality, and the jnp references in
``ref.py`` are the oracles the determinism matrix pins against.

VMEM budget per program instance (TILE_N=256, C≤8192, int32): label/gid
tiles 2 KiB each (8 KiB lane-padded), move list 3·32 KiB (×8 sublane
padding), compare matrix TILE_N×CAND_CHUNK×4 = 128 KiB (CAND_CHUNK=128) —
far inside the envelope and independent of n_local.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.gain.kernel import round_up

I32_MIN = jnp.iinfo(jnp.int32).min
PAD_I32 = jnp.iinfo(jnp.int32).max  # == repro.core.graph.PAD (pinned in tests)


def _apply_kernel(lab_ref, gid_ref, tid_ref, tgt_ref, mov_ref, out_ref, *,
                  cand_chunk: int):
    """fori_loop over candidate chunks: dense gid-compare select of the
    (TILE_N, 1) per-slot global ids against the resident move list."""
    gid = gid_ref[:, :1]
    c_tot = tid_ref.shape[1]

    def body(c, lab):
        sl = pl.ds(c * cand_chunk, cand_chunk)
        t = tid_ref[:1, sl]                    # (1, CC) move ids
        g = tgt_ref[:1, sl]                    # (1, CC) move targets
        mv = mov_ref[:1, sl] != 0              # (1, CC) accepted mask
        m = mv & (t != PAD_I32) & (gid == t)   # (T, CC) match matrix
        hit = jnp.max(m.astype(jnp.int32), axis=1, keepdims=True) > 0
        val = jnp.max(jnp.where(m, g, I32_MIN), axis=1, keepdims=True)
        return jnp.where(hit, val, lab)

    out_ref[:, :] = jax.lax.fori_loop(0, c_tot // cand_chunk, body,
                                      lab_ref[:, :1])


def _pad_moves(tids, tgts, moved, cand_chunk: int):
    c = tids.shape[0]
    c_pad = round_up(max(c, 1), cand_chunk)
    pad = c_pad - c
    tids = jnp.pad(tids.astype(jnp.int32), (0, pad),
                   constant_values=int(PAD_I32))
    tgts = jnp.pad(tgts.astype(jnp.int32), (0, pad))
    mov = jnp.pad(moved.astype(jnp.int32), (0, pad))
    return tids[None, :], tgts[None, :], mov[None, :]


@functools.partial(
    jax.jit, static_argnames=("tile_n", "cand_chunk", "interpret"))
def halo_apply_pallas(labels, gid, tids, tgts, moved, *, tile_n: int = 256,
                      cand_chunk: int = 128, interpret: bool = False):
    """Apply a replayed global move list to owned halo slots.

    ``labels``/``gid`` are (n,) halo-layout labels and per-slot global ids
    (``PAD`` on non-owned slots); ``tids``/``tgts``/``moved`` the (c,)
    gathered move records.  Returns the (n,) updated labels.
    """
    if not interpret and (tile_n % 8 or cand_chunk % 128):
        raise ValueError(f"compiled halo kernel needs tile_n % 8 == 0 and "
                         f"cand_chunk % 128 == 0, got tile_n={tile_n}, "
                         f"cand_chunk={cand_chunk}")
    n = labels.shape[0]
    n_pad = round_up(max(n, 1), tile_n)
    lab = jnp.pad(labels.astype(jnp.int32), (0, n_pad - n))
    gid_p = jnp.pad(gid.astype(jnp.int32), (0, n_pad - n),
                    constant_values=int(PAD_I32))
    tid2, tgt2, mov2 = _pad_moves(tids, tgts, moved, cand_chunk)
    c_pad = tid2.shape[1]

    row = lambda i: (i, 0)
    whole = lambda i: (0, 0)
    out = pl.pallas_call(
        functools.partial(_apply_kernel, cand_chunk=cand_chunk),
        grid=(n_pad // tile_n,),
        in_specs=[
            pl.BlockSpec((tile_n, 1), row),
            pl.BlockSpec((tile_n, 1), row),
            pl.BlockSpec((1, c_pad), whole),
            pl.BlockSpec((1, c_pad), whole),
            pl.BlockSpec((1, c_pad), whole),
        ],
        out_specs=pl.BlockSpec((tile_n, 1), row),
        out_shape=jax.ShapeDtypeStruct((n_pad, 1), jnp.int32),
        interpret=interpret,
    )(lab[:, None], gid_p[:, None], tid2, tgt2, mov2)
    return out[:n, 0]
