"""Unit contract of the halo move-application kernel
(``repro.kernels.halo``): bit-identity of the Pallas kernel against BOTH
jnp oracles — the dense gid-compare it literally computes and the
production range-test + inverse-permutation formulation it replaces — at
lane/tile boundary shapes (ncand 127/128/129, ragged n_local), plus the
envelope fallback rule and the PAD sentinel pin the equivalence argument
rests on.  Everything runs in interpret mode on the CPU; the 17-cell
matrix in tests/test_refine_matrix.py covers the engine-integrated path.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.graph import PAD
from repro.kernels.halo import (
    HALO_MAX_CAND,
    apply_moves,
    halo_apply_range_ref,
    halo_apply_ref,
    resolve_halo,
)
from repro.kernels.halo.kernel import PAD_I32, halo_apply_pallas


def _halo_case(n_local, ncand, seed=0, owned_frac=0.75):
    """A structurally faithful halo-layout shard (HaloComm conventions):
    this PE's global-id block is [gstart, gstart + n_local); only the first
    ``owned_n`` rel-ids are real (the rest land on ~owned slots and must
    drop); ``inv_perm`` scatters rel-ids over the n_local slots; non-owned
    slots carry gid = PAD (match nothing — the equivalence argument's
    load-bearing property).  The move list names each global id at most
    once (the engine's contract), PAD ids fill the unused tail."""
    rng = np.random.default_rng(seed)
    gstart = 1000
    owned_n = max(int(n_local * owned_frac), 1)
    inv_perm = rng.permutation(n_local).astype(np.int32)  # rel id -> slot
    rel = np.arange(n_local)
    owned = np.zeros(n_local, bool)
    owned[inv_perm[rel[:owned_n]]] = True
    gid = np.full(n_local, int(PAD_I32), np.int32)
    gid[inv_perm[rel[:owned_n]]] = gstart + rel[:owned_n]
    labels = rng.integers(0, 8, n_local).astype(np.int32)

    # move list: unique global ids drawn from a window overlapping the
    # block on both sides — out-of-range ids and ids in the ~owned tail of
    # the block must both be dropped
    universe = np.arange(gstart - ncand, gstart + n_local + ncand)
    ids = rng.choice(universe, size=min(ncand, len(universe)), replace=False)
    tids = np.full(ncand, int(PAD_I32), np.int32)
    tids[: len(ids)] = ids
    moved = np.zeros(ncand, np.int32)
    moved[: len(ids)] = (rng.random(len(ids)) < 0.7)
    tgts = rng.integers(0, 8, ncand).astype(np.int32)
    return (jnp.asarray(labels), jnp.asarray(gid), jnp.asarray(tids),
            jnp.asarray(tgts), jnp.asarray(moved), gstart, n_local,
            jnp.asarray(inv_perm), jnp.asarray(owned))


BOUNDARY_NCAND = (127, 128, 129)
RAGGED_N = (300, 511, 513)


@pytest.mark.parametrize("ncand", BOUNDARY_NCAND)
@pytest.mark.parametrize("n_local", RAGGED_N)
def test_apply_kernel_matches_both_refs(n_local, ncand):
    labels, gid, tids, tgts, moved, gstart, n_block, inv_perm, owned = \
        _halo_case(n_local, ncand, seed=n_local * 1000 + ncand)
    out_k = halo_apply_pallas(labels, gid, tids, tgts, moved,
                              tile_n=256, cand_chunk=128, interpret=True)
    out_dense = halo_apply_ref(labels, gid, tids, tgts, moved.astype(bool))
    out_range = halo_apply_range_ref(
        labels, tids, tgts, moved.astype(bool), gstart=gstart,
        n_local=n_block, inv_perm=inv_perm, owned=owned)
    np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_dense))
    np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_range))


@pytest.mark.parametrize("tile_n,cand_chunk", [(128, 64), (256, 128),
                                               (512, 256), (8, 64)])
def test_apply_kernel_tile_invariant(tile_n, cand_chunk):
    """Tile parameters are pure speed knobs — every configuration produces
    the same labels (the property that lets tuned.json change freely)."""
    labels, gid, tids, tgts, moved, *_ = _halo_case(513, 129, seed=3)
    want = halo_apply_ref(labels, gid, tids, tgts, moved.astype(bool))
    got = halo_apply_pallas(labels, gid, tids, tgts, moved,
                            tile_n=tile_n, cand_chunk=cand_chunk,
                            interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_public_ops_match_kernel_entry_points():
    """The ops-layer wrappers (autotune-resolved tiles) compute the same
    labels as explicit-tile kernel calls."""
    labels, gid, tids, tgts, moved, *_ = _halo_case(300, 128, seed=9)
    np.testing.assert_array_equal(
        np.asarray(apply_moves(labels, gid, tids, tgts, moved,
                               interpret=True)),
        np.asarray(halo_apply_ref(labels, gid, tids, tgts,
                                  moved.astype(bool))))


def test_pad_sentinel_pins_core_pad():
    """The kernel's PAD-id guard must agree with the core padding sentinel:
    non-owned halo slots carry gid=PAD, and the equivalence of the dense
    gid-compare with the range-test path rests on PAD matching no move."""
    assert int(PAD_I32) == int(PAD) == np.iinfo(np.int32).max


def test_resolve_halo_fallback_rule():
    assert resolve_halo("auto", 512) == "pallas"
    assert resolve_halo("pallas", 512) == "pallas"
    assert resolve_halo("jnp", 512) == "jnp"
    # envelope: an oversized move list streams through jnp; the labels
    # stream in tiles, so no shard size is out of the envelope
    assert resolve_halo("pallas", HALO_MAX_CAND) == "pallas"
    assert resolve_halo("pallas", HALO_MAX_CAND + 1) == "jnp"
    with pytest.raises(ValueError, match="halo kernel backend"):
        resolve_halo("cuda", 512)


def test_moved_pad_slots_are_inert():
    """A PAD id marked moved=1 (the padded tail) must change nothing —
    the kernel's `t != PAD` guard, not just the moved mask, protects it."""
    labels, gid, tids, tgts, moved, *_ = _halo_case(300, 127, seed=5)
    moved_hot = jnp.where(tids == PAD_I32, 1, moved).astype(jnp.int32)
    got = halo_apply_pallas(labels, gid, tids, tgts, moved_hot,
                            tile_n=256, cand_chunk=128, interpret=True)
    want = halo_apply_pallas(labels, gid, tids, tgts, moved,
                             tile_n=256, cand_chunk=128, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
