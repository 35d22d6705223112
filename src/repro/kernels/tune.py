"""Tile autotuner for the Pallas kernels (gain scoreboard + halo move application).

The kernels' tile parameters (TILE_N, DEG_CHUNK, CAND_CHUNK) never change
*results* — padding rows/columns are inert by construction and the tile
sweep is parity-pinned in tests — so they are pure speed knobs.  This
module owns their resolution:

  * :func:`lookup` — consulted at **trace time** by ``refine/gain.py`` and
    ``kernels/halo/ops.py`` when a tile parameter is left ``None``.  It
    reads the committed ``tuned.json`` next to this file ONCE per process
    (module-level cache) and resolves by bucket key, so repeated traces of
    the same level shape see the same configuration and the drivers'
    ``lru_cache`` keys never need to carry tile parameters.
  * :func:`autotune` — sweeps the configuration space against the timing
    primitives in ``benchmarks/kernel_bench.py`` (lazy import: benchmarks
    depend on the kernels, not the other way around) and persists the best
    configurations.  Regeneration workflow: see benchmarks/README.md.

Bucket key: ``<backend>/n<2^⌈log₂ n⌉>-d<2^⌈log₂ d⌉>-k<K padded to 128>``
— (backend, n-bucket, max_deg-bucket, K-lane).  ``d`` is the padded
adjacency width for the gain kernel and the move-list length for the halo
kernel; ``backend`` is ``"tpu"`` for compiled Mosaic and ``"interpret"``
everywhere else (this container), so a table tuned off-TPU never leaks
onto hardware — unknown keys fall back to the hardcoded defaults.

A missing, unreadable, version-skewed or value-invalid table degrades to
:data:`DEFAULTS` silently (partitions are tile-invariant, so this is a
perf regression at worst — tests/test_kernel_tune.py pins the contract).
"""

from __future__ import annotations

import json
from pathlib import Path

import jax

TUNED_VERSION = 1
TUNED_PATH = Path(__file__).parent / "tuned.json"

DEFAULTS = {
    "gain": {"tile_n": 256, "deg_chunk": 16},
    "halo": {"tile_n": 256, "cand_chunk": 128},
}

# swept configuration space (autotune); kept small — the bucket table, not
# the sweep, is what production consults
SWEEP = {
    "gain": {"tile_n": (128, 256, 512), "deg_chunk": (8, 16, 32)},
    "halo": {"tile_n": (128, 256, 512), "cand_chunk": (64, 128, 256)},
}

_CACHE: dict[str, dict] = {}


def backend_name(interpret: bool | None = None) -> str:
    """The backend axis of the bucket key: compiled Mosaic vs interpret."""
    from repro.kernels import default_interpret

    on_tpu = jax.default_backend() == "tpu"
    return "tpu" if (on_tpu and not default_interpret(interpret)) \
        else "interpret"


def _pow2_bucket(x: int) -> int:
    x = max(int(x), 1)
    return 1 << (x - 1).bit_length()


def bucket_key(kernel: str, *, n: int, d: int, k: int,
               backend: str | None = None) -> str:
    if kernel not in DEFAULTS:
        raise ValueError(f"unknown kernel {kernel!r}; have {sorted(DEFAULTS)}")
    backend = backend_name() if backend is None else backend
    k_lane = -(-max(int(k), 1) // 128) * 128
    return f"{backend}/n{_pow2_bucket(n)}-d{_pow2_bucket(d)}-k{k_lane}"


def _valid_config(kernel: str, cfg) -> bool:
    """A usable table entry: every tile knob of the kernel present, a
    positive int, and TILE_N sublane-aligned (multiple of 8)."""
    if not isinstance(cfg, dict):
        return False
    for key in DEFAULTS[kernel]:
        v = cfg.get(key)
        if not isinstance(v, int) or isinstance(v, bool) or v <= 0:
            return False
        if key == "tile_n" and v % 8 != 0:
            return False
    return True


def load_tuned(path: str | Path | None = None) -> dict:
    """Parse a tuned table, degrading to ``{}`` on any defect (missing
    file, bad JSON, version skew).  Cached per path for the process
    lifetime — the trace-time determinism contract."""
    p = str(TUNED_PATH if path is None else path)
    if p not in _CACHE:
        table: dict = {}
        try:
            raw = json.loads(Path(p).read_text())
            if isinstance(raw, dict) and raw.get("version") == TUNED_VERSION:
                table = raw
        except (OSError, ValueError):
            table = {}
        _CACHE[p] = table
    return _CACHE[p]


def clear_cache() -> None:
    """Drop the per-process table cache (tests only — production relies on
    the cache for stable trace-time lookups)."""
    _CACHE.clear()


def lookup(kernel: str, *, n: int, d: int, k: int,
           backend: str | None = None,
           path: str | Path | None = None) -> dict:
    """Best-known tile configuration for a kernel shape, or the hardcoded
    defaults when the table has no (valid) entry for its bucket."""
    entry = load_tuned(path).get(kernel, {})
    cfg = entry.get(bucket_key(kernel, n=n, d=d, k=k, backend=backend)) \
        if isinstance(entry, dict) else None
    base = dict(DEFAULTS[kernel])
    if _valid_config(kernel, cfg):
        base.update({kk: cfg[kk] for kk in base})
    return base


def sweep_configs(kernel: str):
    """The autotune candidate grid, defaults first (ties keep the
    default)."""
    space = SWEEP[kernel]
    keys = sorted(space)
    grid = [{}]
    for kk in keys:
        grid = [dict(g, **{kk: v}) for g in grid for v in space[kk]]
    default = DEFAULTS[kernel]
    grid.sort(key=lambda g: g != default)  # stable: default leads
    return grid


def autotune(kernels=("gain", "halo"), *, shapes=None, reps: int = 3,
             path: str | Path | None = None, verbose: bool = False) -> dict:
    """Sweep every (kernel, shape) pair and persist the winners.

    Measurement lives in ``benchmarks/kernel_bench.py`` (its ``SHAPES``
    table is the default shape set); this function only owns the argmin
    and the table format.  Returns the written table.
    """
    from benchmarks import kernel_bench as kb

    table: dict = {"version": TUNED_VERSION}
    backend = backend_name()
    for kernel in kernels:
        table[kernel] = {}
        for shape in (shapes or kb.SHAPES[kernel]):
            best_cfg, best_t = None, float("inf")
            for cfg in sweep_configs(kernel):
                t = kb.measure(kernel, shape, cfg, reps=reps)
                if verbose:
                    print(f"  {kernel} {shape['name']} {cfg}: {t*1e6:.1f}us")
                if t < best_t:
                    best_cfg, best_t = cfg, t
            key = bucket_key(kernel, n=shape["n"], d=shape["d"],
                             k=shape["k"], backend=backend)
            table[kernel][key] = dict(best_cfg, us=round(best_t * 1e6, 2))
    out = Path(TUNED_PATH if path is None else path)
    out.write_text(json.dumps(table, indent=1, sort_keys=True) + "\n")
    _CACHE.pop(str(out), None)
    return table
