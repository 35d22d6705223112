#!/usr/bin/env python3
"""The control of the check: a plain reference partitioner put in the
program's place, once at the precision the configuration states and once
a step below it.

The configuration's guarantees rest on exact sums: block weights, gains
and the cut are fp32 sums of integer weights below 2^24 (DESIGN section 1).
The step that would tempt a later change is bfloat16.  The reference
partitioner walks the vertices in order and fills block after block up to
ceil(n / k), keeping each block's weight in a running counter; it reports
the cut and the imbalance of its labels, summed in the same type.

* float32: every counter is exact, every block weighs ceil(n / k) or
  less, and the reported numbers equal the check's.  The check passes.
* bfloat16: a counter stops growing at 256 (256 + 1 rounds back to 256),
  so the first block never fills and takes every vertex.  The balance
  guarantee breaks, and the check has to fail.

    python3 bench/control.py --workload rgg2d-n17-k16.oneshot \
        --seeds 11 12 13

runs both on whatever device JAX has, at the cell's own size, and prints
the check's numbers for each seed; the benchmark's runs never run it.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import check, graphs  # noqa: E402


def reference_answer(graph, k: int, dtype):
    """``(labels, cut, imbalance)`` of the fill-in-order partition, every
    sum kept in ``dtype``."""
    import jax
    import jax.numpy as jnp

    n, a, b, w = graph
    cap = jnp.asarray(-(-n // k), dtype)
    one = jnp.asarray(1, dtype)

    def place(carry, _):
        blk, weight = carry
        full = (weight[blk] + one > cap) & (blk < k - 1)
        blk = jnp.where(full, blk + 1, blk)
        weight = weight.at[blk].add(one)
        return (blk, weight), blk

    (_, weight), labels = jax.lax.scan(
        place, (jnp.int32(0), jnp.zeros((k,), dtype)), None, length=n)
    la, lb = labels[jnp.asarray(a)], labels[jnp.asarray(b)]
    cut = jnp.sum(jnp.where(la != lb, jnp.asarray(w).astype(dtype),
                            jnp.zeros((), dtype)))
    imb = jnp.max(weight) / jnp.asarray(n / k, dtype) - one
    return np.asarray(labels), float(cut), float(imb)


def readings(workload: str, seeds, manifest_path=ROOT / "BENCHMARK.json"):
    """``{dtype: [numbers per seed]}`` for float32 and bfloat16."""
    import jax.numpy as jnp

    from bench.harness import load_cell

    _, config, _, _, _ = load_cell(workload, Path(manifest_path))
    part = config["partition"]
    out = {"float32": [], "bfloat16": []}
    for seed in seeds:
        n, u, v = graphs.generate(config["graph"], seed)
        graph = (n, *graphs.edge_list(u, v))
        for name, dtype in (("float32", jnp.float32),
                            ("bfloat16", jnp.bfloat16)):
            labels, cut, imb = reference_answer(graph, part["k"], dtype)
            nums = check.check_answer(graph, part["k"], part["eps"], labels,
                                      cut, imb)
            nums["correct"] = check.verdict(nums, config["limits"])
            out[name].append({"seed": seed, **nums})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    import jax

    dev = jax.devices()[0]
    res = readings(args.workload, args.seeds)
    print(json.dumps({"workload": args.workload,
                      "device": [dev.platform, dev.device_kind],
                      "readings": res}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
