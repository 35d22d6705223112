"""Kernel microbenchmark + the autotuner's timing primitive.

Two Pallas kernels are timed (interpret mode on CPU — a correctness /
relative-cost sweep, not TPU performance; compiled Mosaic numbers come
from running the same entry points on hardware):

  * ``gain`` — the VMEM scoreboard (``kernels/gain``): dense (K, TILE_N)
    gain tile accumulated one neighbour row at a time.
  * ``halo`` — the move-application kernel (``kernels/halo``): the
    O(P·ncand) gid-compare move pass in one ``pallas_call``.

This module owns the measured side of the autotune loop:
:data:`SHAPES` is the default shape set and :func:`measure` the timing
primitive that ``repro.kernels.tune.autotune`` sweeps tile configurations
against (returns *seconds* per call).  Inputs are built deterministically
per shape (seeded numpy) and memoised, so a sweep times kernels, not
input generation.

As a CLI it emits a schema-versioned ``KERNEL_bench.json`` — per
(kernel, shape): the hardcoded-default config timing, the committed
``tuned.json`` config timing, and the ``wins`` table recording the
measured default-vs-tuned speedup (CI's kernel-smoke gate validates the
document via ``benchmarks.common.validate_kernel_bench``):

    PYTHONPATH=src:. python benchmarks/kernel_bench.py --smoke --out KERNEL_bench.json
    PYTHONPATH=src:. python benchmarks/kernel_bench.py --sweep   # full grid

Via ``benchmarks.run`` (``python -m benchmarks.run kernel``) it emits the
same timings as CSV rows plus the analytic v5e roofline terms for the
largest gain shape.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import tune
from repro.kernels.gain.kernel import gain_scoreboard_pallas, round_up
from repro.kernels.halo.kernel import halo_apply_pallas

HERE = os.path.dirname(os.path.abspath(__file__))

# (kernel → shape dicts): n = rows (vertices / shard slots), d = padded
# degree (gain) or move-list candidates (halo), k = blocks (gain; the halo
# kernel is k-free → 1).  Buckets are distinct so each shape lands in its
# own tuned.json entry.
SHAPES = {
    "gain": [
        {"name": "n4k_d32_k8", "n": 4096, "d": 32, "k": 8},
        {"name": "n16k_d64_k64", "n": 16384, "d": 64, "k": 64},
    ],
    "halo": [
        {"name": "n4k_c1k", "n": 4096, "d": 1024, "k": 1},
        {"name": "n16k_c4k", "n": 16384, "d": 4096, "k": 1},
    ],
}

# the CI kernel-smoke grid: one small shape per kernel (interpret mode is
# Python-evaluated — seconds per config, so the smoke doc times only the
# default and tuned configs, not the full sweep)
SMOKE_SHAPES = {
    "gain": [{"name": "smoke_n512_d16_k8", "n": 512, "d": 16, "k": 8}],
    "halo": [{"name": "smoke_n512_c128", "n": 512, "d": 128, "k": 1}],
}

_INPUT_MEMO: dict = {}


def _gain_inputs(shape, tile_n: int, deg_chunk: int):
    """Deterministic padded-adjacency inputs for the scoreboard kernel,
    memoised per (shape, padded dims)."""
    from repro.core.graph import PAD

    n, d, k = shape["n"], shape["d"], shape["k"]
    n_pad = round_up(n, tile_n)
    d_pad = round_up(d, deg_chunk)
    k_pad = round_up(k, 128)
    key = ("gain", shape["name"], n_pad, d_pad, k_pad)
    if key not in _INPUT_MEMO:
        rng = np.random.default_rng(7)
        # neighbour-major (D, N) layout, the kernel's input contract
        nbr_lab = rng.integers(0, k, (d_pad, n_pad), dtype=np.int32)
        nbr_lab[rng.random((d_pad, n_pad)) < 0.1] = int(PAD)  # ragged rows
        nbr_lab[d:, :] = int(PAD)
        nbr_w = rng.integers(1, 5, (d_pad, n_pad)).astype(np.float32)
        lab = rng.integers(0, k, (n_pad,), dtype=np.int32)
        nw = rng.integers(1, 4, (n_pad,)).astype(np.float32)
        cap = np.full((k_pad,), -np.inf, np.float32)
        cap[:k] = np.inf
        _INPUT_MEMO[key] = tuple(jnp.asarray(a)
                                 for a in (nbr_lab, nbr_w, lab, nw, cap))
    return _INPUT_MEMO[key]


def _halo_inputs(shape):
    """Deterministic halo-layout inputs for the move kernel (labels,
    per-slot global ids, move list), memoised per shape.  Pad-independent:
    the jit wrapper pads to the tile grid."""
    n, c = shape["n"], shape["d"]
    key = ("halo", shape["name"])
    if key not in _INPUT_MEMO:
        rng = np.random.default_rng(11)
        lab = rng.integers(0, 8, (n,), dtype=np.int32)
        perm = rng.permutation(n).astype(np.int32)
        gid = np.arange(n, dtype=np.int32)[perm]
        tids = rng.integers(0, n, (c,), dtype=np.int32)
        tgts = rng.integers(0, 8, (c,), dtype=np.int32)
        moved = (rng.random((c,)) < 0.5).astype(np.int32)
        _INPUT_MEMO[key] = tuple(jnp.asarray(a)
                                 for a in (lab, gid, tids, tgts, moved))
    return _INPUT_MEMO[key]


def _bench_case(kernel: str, shape, cfg):
    """(thunk,) closure running one kernel call for this shape/config."""
    interpret = jax.default_backend() != "tpu"
    if kernel == "gain":
        nbr_lab, nbr_w, lab, nw, cap = _gain_inputs(
            shape, cfg["tile_n"], cfg["deg_chunk"])
        return lambda: gain_scoreboard_pallas(
            nbr_lab, nbr_w, lab, nw, cap, tile_n=cfg["tile_n"],
            deg_chunk=cfg["deg_chunk"], interpret=interpret)
    if kernel == "halo":
        lab, gid, tids, tgts, moved = _halo_inputs(shape)
        return lambda: halo_apply_pallas(
            lab, gid, tids, tgts, moved, tile_n=cfg["tile_n"],
            cand_chunk=cfg["cand_chunk"], interpret=interpret)
    raise ValueError(f"unknown kernel {kernel!r}; have {sorted(SHAPES)}")


def measure(kernel: str, shape, cfg=None, reps: int = 3) -> float:
    """Seconds per call of one (kernel, shape, tile-config) case — the
    autotuner's primitive (``tune.autotune``).  Partial configs are merged
    over the kernel's defaults; the first (compile/trace) call is
    excluded; the min over ``reps`` is returned (the standard
    microbenchmark estimator — least scheduling noise)."""
    cfg = {**tune.DEFAULTS[kernel], **(cfg or {})}
    thunk = _bench_case(kernel, shape, cfg)
    jax.tree.leaves(thunk())[0].block_until_ready()  # compile + input build
    best = float("inf")
    for _ in range(max(int(reps), 1)):
        t0 = time.perf_counter()
        out = thunk()
        jax.tree.leaves(out)[0].block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best


def _cell(kernel, shape, backend, source, cfg, seconds):
    return {
        "kernel": kernel, "shape": shape["name"], "n": shape["n"],
        "d": shape["d"], "k": shape["k"], "backend": backend,
        "source": source, "config": {kk: cfg[kk] for kk in
                                     tune.DEFAULTS[kernel]},
        "us": seconds * 1e6,
    }


def build_doc(shapes=None, reps: int = 3, smoke: bool = False,
              sweep: bool = False, verbose: bool = True) -> dict:
    """Time every (kernel, shape) at the default and tuned configs (plus
    the full sweep grid with ``sweep=True``) and assemble the
    KERNEL_bench.json document, including the ``wins`` default-vs-best
    table the autotune acceptance reads."""
    from benchmarks.common import KERNEL_BENCH_SCHEMA_VERSION

    shapes = shapes or (SMOKE_SHAPES if smoke else SHAPES)
    backend = tune.backend_name()
    cells, wins = [], {}
    for kernel in sorted(shapes):
        for shape in shapes[kernel]:
            default_cfg = dict(tune.DEFAULTS[kernel])
            tuned_cfg = tune.lookup(kernel, n=shape["n"], d=shape["d"],
                                    k=shape["k"], backend=backend)
            t_def = measure(kernel, shape, default_cfg, reps=reps)
            cells.append(_cell(kernel, shape, backend, "default",
                               default_cfg, t_def))
            best_cfg, t_best = default_cfg, t_def
            if tuned_cfg != default_cfg:
                t_tuned = measure(kernel, shape, tuned_cfg, reps=reps)
                cells.append(_cell(kernel, shape, backend, "tuned",
                                   tuned_cfg, t_tuned))
                if t_tuned < t_best:
                    best_cfg, t_best = tuned_cfg, t_tuned
            if sweep:
                for cfg in tune.sweep_configs(kernel):
                    if cfg in (default_cfg, tuned_cfg):
                        continue
                    t = measure(kernel, shape, cfg, reps=reps)
                    cells.append(_cell(kernel, shape, backend, "sweep",
                                       cfg, t))
                    if t < t_best:
                        best_cfg, t_best = cfg, t
            wins[f"{kernel}/{shape['name']}"] = {
                "default_us": t_def * 1e6,
                "best_us": t_best * 1e6,
                "best_config": {kk: best_cfg[kk]
                                for kk in tune.DEFAULTS[kernel]},
                "speedup": t_def / max(t_best, 1e-12),
            }
            if verbose:
                w = wins[f"{kernel}/{shape['name']}"]
                print(f"  {kernel:5s} {shape['name']:18s} default "
                      f"{w['default_us']:9.1f}us  best "
                      f"{w['best_us']:9.1f}us  "
                      f"({w['speedup']:.2f}x, {w['best_config']})",
                      flush=True)
    return {
        "schema_version": KERNEL_BENCH_SCHEMA_VERSION,
        "smoke": bool(smoke),
        "backend": backend,
        "versions": {"jax": jax.__version__, "numpy": np.__version__,
                     "python": sys.version.split()[0]},
        "cells": cells,
        "wins": wins,
    }


def main(emit):
    """benchmarks.run entry point: CSV rows (name, us_per_call, derived =
    rows/us throughput) + the analytic v5e roofline terms."""
    doc = build_doc(smoke=True, reps=3, verbose=False)
    for c in doc["cells"]:
        emit(f"kernel.{c['kernel']}.{c['shape']}.{c['source']}",
             c["us"], c["n"] / max(c["us"], 1e-9))

    # analytic kernel roofline on v5e for the largest gain shape (§Roofline)
    from repro.roofline import phase_roofline

    shape = SHAPES["gain"][-1]
    n, d = shape["n"], shape["d"]
    kp = round_up(shape["k"], 128)
    flops = 3.0 * n * d * kp             # compare+select+accumulate per cell
    bytes_ = n * d * 8 + n * kp * 4
    roof = phase_roofline(flops, bytes_, 1.0, hw="v5e")
    emit("kernel.gain.v5e_compute_us", 0, flops / 197e12 * 1e6)
    emit("kernel.gain.v5e_memory_us", 0, bytes_ / 819e9 * 1e6)
    emit("kernel.gain.v5e_intensity_flops_per_byte", 0,
         roof["flops"] / max(roof["bytes"], 1e-9))


def cli(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shape set, default+tuned configs only "
                         "(the CI kernel-smoke job)")
    ap.add_argument("--sweep", action="store_true",
                    help="time the full tile-config grid per shape")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--out", default=os.path.join(HERE, "KERNEL_bench.json"))
    args = ap.parse_args(argv)

    from benchmarks.common import validate_kernel_bench

    doc = build_doc(reps=args.reps, smoke=args.smoke, sweep=args.sweep)
    violations = validate_kernel_bench(doc)
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"wrote {args.out} ({len(doc['cells'])} cells, "
          f"backend={doc['backend']})")
    for msg in violations:
        print(f"SCHEMA VIOLATION: {msg}", file=sys.stderr)
    return 0 if not violations else 1


if __name__ == "__main__":
    sys.exit(cli())
