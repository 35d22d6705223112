"""Pure-jnp oracles for the halo move-application kernel.

Two independent references pin the move-application kernel:

  * :func:`halo_apply_ref` — the dense gid-compare in jnp, the literal
    arithmetic the Pallas kernel runs (one (n, c) match matrix);
  * :func:`halo_apply_range_ref` — the production jnp path's range-test +
    inverse-permutation formulation, kept verbatim from
    ``HaloComm.apply_moves`` so the equivalence argument (module docstring
    of ``kernel.py``) is itself under test, not just asserted.

Both return bit-identical int32 labels for every move list the engine can
emit (each global id moved at most once, real ids only).
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.halo.kernel import PAD_I32


def halo_apply_ref(labels, gid, tids, tgts, moved):
    """Dense gid-compare oracle: slot i takes tgts[j] iff moved[j] and
    tids[j] == gid[i] (PAD ids match nothing)."""
    m = (moved[None, :] & (tids[None, :] != PAD_I32)
         & (gid[:, None] == tids[None, :]))                  # (n, c)
    hit = jnp.any(m, axis=1)
    val = jnp.max(jnp.where(m, tgts[None, :],
                            jnp.iinfo(jnp.int32).min), axis=1)
    return jnp.where(hit, val, labels).astype(jnp.int32)


def halo_apply_range_ref(labels, tids, tgts, moved, *, gstart, n_local,
                         inv_perm, owned):
    """The range-test + inv_perm formulation (HaloComm.apply_moves's jnp
    path, verbatim): ownership is a range test against this PE's
    contiguous global-id block, the halo slot one gather through
    ``inv_perm``; ids landing on non-owned slots are dropped."""
    rel = tids - gstart
    inb = moved & (rel >= 0) & (rel < n_local)
    slot = inv_perm[jnp.where(inb, rel, 0)]
    ok = inb & owned[slot]
    idx = jnp.where(ok, slot, n_local)
    return labels.at[idx].set(tgts, mode="drop")
