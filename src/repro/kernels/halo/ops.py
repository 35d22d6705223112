"""Public dispatch for the halo move-application kernel (DESIGN.md §5).

Mirrors the gain scoreboard's contract: ``resolve_halo`` applies the VMEM
envelope fallback rule (requests outside it silently stream through the
jnp path — the partition is bit-identical either way), the wrapper runs in
interpret mode off-TPU, and tile parameters left ``None`` are resolved
from the committed autotune table (``repro.kernels.tune``) at trace time.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import default_interpret
from repro.kernels.halo.kernel import halo_apply_pallas

# VMEM envelope (DESIGN.md §5): the move list lives whole in VMEM as (1, C)
# lane vectors; the labels stream in tiles, so n_local is unbounded.
HALO_MAX_CAND = 8192


def resolve_halo(kind: str, ncand: int) -> str:
    """Apply the fallback rule: the halo kernel serves move lists of
    ≤ ``HALO_MAX_CAND`` candidates; anything larger keeps the XLA
    gather/scatter path.  ``kind`` is the same backend switch as the gain
    kernel ("jnp" / "pallas" / "auto")."""
    if kind == "auto":
        kind = "pallas"
    if kind not in ("jnp", "pallas"):
        raise ValueError(
            f"halo kernel backend must be 'jnp', 'pallas' or 'auto', got {kind!r}")
    if kind == "pallas" and ncand > HALO_MAX_CAND:
        return "jnp"
    return kind


def apply_moves(labels, gid, tids, tgts, moved, *, tile_n: int | None = None,
                cand_chunk: int | None = None, interpret: bool | None = None):
    """Move application on halo-layout labels (see ``kernel.py``).

    The hot-path entry used by ``HaloComm.apply_moves`` when the kernel
    backend is active; shapes need no pre-padding (the wrapper pads to the
    tile grid and slices back).
    """
    from repro.kernels.tune import lookup

    cfg = lookup("halo", n=labels.shape[0], d=tids.shape[0], k=1)
    return halo_apply_pallas(
        labels, gid, tids, tgts, moved.astype(jnp.int32),
        tile_n=cfg["tile_n"] if tile_n is None else tile_n,
        cand_chunk=cfg["cand_chunk"] if cand_chunk is None else cand_chunk,
        interpret=default_interpret(interpret))
