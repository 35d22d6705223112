"""Bytes each Pallas kernel on the path must move, from its shapes.

The gain scoreboard kernel (``kernels/gain``) reads the neighbour-major
padded adjacency, (D, N) neighbour labels (int32) and (D, N) edge weights
(fp32), the (1, N) own labels and vertex weights, and the (K, 1) block
capacities once, and writes three (1, N) outputs (own, gain, target):

    bytes = 4 * (2 * D * N + 2 * N + K + 3 * N)

Its arithmetic (D * K compare-adds per vertex on the VPU) has no published
peak to set against, so its roofline share is the bytes bound alone.
"""

from __future__ import annotations

import re

# the name XLA gives the kernel's custom call (its jitted wrapper)
GAIN_KERNEL = "gain_scoreboard"

# XLA's HLO text of the call lists the operand shapes in its
# operand_layout_constraints={s32[D,N]{1,0}, ..., f32[K,1]{1,0}}
_OPERANDS = re.compile(
    r"operand_layout_constraints=\{((?:[^{}]|\{[^{}]*\})*)\}")
_SHAPE = re.compile(r"\b[a-z]+\d*\[(\d+(?:,\d+)*)\]")


def gain_kernel_bytes(d: int, n: int, k: int) -> int:
    return 4 * (2 * d * n + 2 * n + k + 3 * n)


def gain_call_shapes(hlo_text: str):
    """``(D, N, K)`` from the HLO text of one gain-kernel call: the first
    operand is the (D, N) neighbour labels, the last the (K, 1) capacities.
    None where the text names no such operands."""
    m = _OPERANDS.search(hlo_text)
    if not m:
        return None
    shapes = [tuple(int(x) for x in s.split(","))
              for s in _SHAPE.findall(m.group(1))]
    if len(shapes) != 5 or len(shapes[0]) != 2 or len(shapes[-1]) != 2:
        return None
    (d, n), (k, _) = shapes[0], shapes[-1]
    return d, n, k
