"""Roofline-term extraction from a compiled (dry-run) executable.

Three terms per (arch × shape × mesh), in seconds:

    compute    = HLO_FLOPs_per_device / peak_FLOPs
    memory     = HLO_bytes_per_device / HBM_bw
    collective = collective_wire_bytes_per_device / ICI_bw

FLOPs/bytes come from ``compiled.cost_analysis()`` (the SPMD module is the
per-device program, so the numbers are already per-device).  Collective bytes
are NOT in cost_analysis: we parse the optimized HLO and sum result-shape
sizes of every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute, weighted by the ring-algorithm wire factor:

    all-gather      1.0 × result      (each device receives result−shard)
    reduce-scatter  1.0 × operand     (symmetric)
    all-reduce      2.0 × operand     (RS + AG)
    all-to-all      1.0
    collective-permute 1.0

Hardware constants default to those of the attached device (TPU v5e: 197
bf16 TFLOP/s, 819 GB/s HBM, ~50 GB/s/link ICI).  Every entry point takes an
``hw=`` override — a preset name from :data:`HW_PRESETS` or a dict with
the three ``peak_flops``/``hbm_bw``/``ici_bw`` keys, validated eagerly by
:func:`resolve_hw` (a missing key or non-positive value fails with the
offending field named, instead of a KeyError deep in the ratio math).
"""

from __future__ import annotations

import dataclasses
import math
import re
from typing import Any

HW_V5E = {
    "peak_flops": 197e12,   # bf16 FLOP/s per chip
    "hbm_bw": 819e9,        # B/s per chip
    "ici_bw": 50e9,         # B/s per link
}

HW_V5P = {
    "peak_flops": 459e12,
    "hbm_bw": 2765e9,
    "ici_bw": 100e9,
}

# interpret-mode runs on this container have no meaningful peak, but the
# bench schema wants finite fractions: one nominal server core
HW_CPU = {
    "peak_flops": 100e9,
    "hbm_bw": 20e9,
    "ici_bw": 10e9,
}

HW_PRESETS = {"v5e": HW_V5E, "v5p": HW_V5P, "cpu": HW_CPU}

HW_KEYS = ("peak_flops", "hbm_bw", "ici_bw")


# jax ``device_kind`` → preset: what ``resolve_hw(None)`` reads off the
# attached device.  A kind missing here is an error, never a default.
DEVICE_KIND_PRESETS = {"TPU v5 lite": "v5e", "TPU v5e": "v5e",
                       "TPU v5": "v5p", "TPU v5p": "v5p"}


def resolve_hw(hw) -> dict:
    """Resolve/validate an ``hw=`` argument: ``None`` → the preset of the
    attached device (``jax.devices()[0].device_kind`` looked up in
    :data:`DEVICE_KIND_PRESETS`), a preset name from :data:`HW_PRESETS`,
    or a dict carrying all of :data:`HW_KEYS` as positive finite numbers.
    Raises ``ValueError`` naming the defect — including a device kind with
    no preset, so a roofline is never silently computed against another
    chip's peaks."""
    if hw is None:
        import jax

        kind = jax.devices()[0].device_kind
        if kind not in DEVICE_KIND_PRESETS:
            raise ValueError(
                f"no roofline preset for device kind {kind!r}; pass hw= "
                f"explicitly (presets: {sorted(HW_PRESETS)})")
        return dict(HW_PRESETS[DEVICE_KIND_PRESETS[kind]])
    if isinstance(hw, str):
        if hw not in HW_PRESETS:
            raise ValueError(
                f"unknown hw preset {hw!r}; presets are {sorted(HW_PRESETS)}")
        return dict(HW_PRESETS[hw])
    if not isinstance(hw, dict):
        raise ValueError(f"hw must be None, a preset name or a dict, "
                         f"got {type(hw).__name__}")
    for key in HW_KEYS:
        v = hw.get(key)
        if not isinstance(v, (int, float)) or isinstance(v, bool) \
                or not math.isfinite(v) or v <= 0:
            raise ValueError(
                f"hw[{key!r}]={v!r} invalid: every of {HW_KEYS} must be a "
                f"positive finite number")
    return dict(hw)


_DTYPE_BYTES = {
    "pred": 1, "s2": 1, "u2": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "tf32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
    "f8e4m3": 1, "f8e4m3fn": 1, "f8e4m3fnuz": 1, "f8e4m3b11fnuz": 1,
    "f8e3m4": 1, "f8e5m2": 1, "f8e5m2fnuz": 1, "f8e8m0fnu": 1,
    "f4e2m1fn": 1,
}

# a token that *looks like* an HLO element type (so an unknown one is a
# table gap to fix, not sharding/annotation noise like "devices=[2,1]")
_DTYPE_LIKE = re.compile(r"^(?:pred|bf16|tf32|[sufc]\d+|f8e\w+|f4e\w+)$")

_COLL_FACTORS = {
    "all-gather": 1.0,
    "all-reduce": 2.0,
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}

# `bf16[8,128,4096]{2,1,0}` or tuple results `(f32[...], s32[...])`
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
# shape group must tolerate tuple results with /*index=N*/ comments
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.*?)\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(",
)


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            if _DTYPE_LIKE.match(dt):
                # a real element type the table doesn't know: silently
                # skipping it would under-count wire bytes — fail loudly
                raise ValueError(
                    f"HLO element type {dt!r} (in {shape_str!r}) missing "
                    f"from the roofline dtype table — add its byte width "
                    f"to repro.roofline.analysis._DTYPE_BYTES")
            continue  # annotation noise (e.g. sharding devices=[...])
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collective_bytes(hlo_text: str, body_scale: float = 1.0) -> dict[str, float]:
    """Sum wire bytes by collective kind from optimized HLO text.

    ``body_scale`` > 1 multiplies collectives that live inside while-loop
    *bodies* (scan-mode lowering executes those per layer-stack iteration but
    the text contains them once).  The unrolled dry-run uses 1.0."""
    body_names = set()
    for m in re.finditer(r"body=%?([\w.\-]+)", hlo_text):
        body_names.add(m.group(1))

    out: dict[str, float] = {k: 0.0 for k in _COLL_FACTORS}
    current_comp = ""
    comp_re = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\([^)]*\)\s*->")
    for line in hlo_text.splitlines():
        cm = comp_re.match(line)
        if cm:
            current_comp = cm.group(1)
        m = _OP_RE.match(line)
        if not m:
            continue
        if "-done(" in line:
            continue  # async pair: count only the -start
        shape_str, kind = m.group(1), m.group(2)
        scale = body_scale if current_comp in body_names else 1.0
        out[kind] += _shape_bytes(shape_str) * _COLL_FACTORS[kind] * scale
    return out


@dataclasses.dataclass
class Roofline:
    flops: float                 # per-device HLO flops
    hbm_bytes: float             # per-device bytes accessed
    coll_bytes: dict[str, float]
    compute_s: float
    memory_s: float
    collective_s: float
    bottleneck: str
    model_flops: float           # analytic 6·N·D (or decode analogue), global
    useful_ratio: float          # model_flops / (flops × n_chips)
    peak_fraction: float         # compute_s / max(all terms) when compute-bound
    mem_per_device: dict[str, float]

    def terms(self):
        return {
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
        }


def analyze_compiled(compiled, n_chips: int, model_flops: float,
                     hw=None, body_scale: float = 1.0) -> Roofline:
    hw = resolve_hw(hw)
    cost = compiled.cost_analysis()
    if isinstance(cost, list):  # older jax returns [dict]
        cost = cost[0]
    flops = float(cost.get("flops", 0.0))
    hbm_bytes = float(cost.get("bytes accessed", 0.0))

    hlo = compiled.as_text()
    coll = parse_collective_bytes(hlo, body_scale=body_scale)
    coll_total = sum(coll.values())

    compute_s = flops / hw["peak_flops"]
    memory_s = hbm_bytes / hw["hbm_bw"]
    collective_s = coll_total / hw["ici_bw"]

    terms = {"compute": compute_s, "memory": memory_s, "collective": collective_s}
    bottleneck = max(terms, key=terms.get)
    total_flops = flops * n_chips
    useful = model_flops / total_flops if total_flops else 0.0
    dominant = max(terms.values()) or 1e-30
    peak_fraction = compute_s / dominant

    try:
        ma = compiled.memory_analysis()
        mem = {
            "argument_bytes": float(getattr(ma, "argument_size_in_bytes", 0)),
            "output_bytes": float(getattr(ma, "output_size_in_bytes", 0)),
            "temp_bytes": float(getattr(ma, "temp_size_in_bytes", 0)),
            "generated_code_bytes": float(getattr(ma, "generated_code_size_in_bytes", 0)),
        }
    except Exception:  # pragma: no cover - backend without memory_analysis
        mem = {}

    return Roofline(
        flops=flops,
        hbm_bytes=hbm_bytes,
        coll_bytes=coll,
        compute_s=compute_s,
        memory_s=memory_s,
        collective_s=collective_s,
        bottleneck=bottleneck,
        model_flops=model_flops,
        useful_ratio=useful,
        peak_fraction=peak_fraction,
        mem_per_device=mem,
    )


def partition_phase_model(n: int, m: int, k: int, levels: int,
                          rounds: float = 6.0) -> dict[str, dict[str, float]]:
    """Analytic lower-bound work model for the three partition phases.

    The multilevel hierarchy is geometric, so totals over all levels are
    ≈ 2× the finest level (n_tot ≈ 2n, m_tot ≈ 2m directed edge slots).
    Per phase, counting each mandatory touch of the edge/vertex arrays
    once:

      coarsen — one matching sweep plus one contraction, both streaming
                the edge list: 4·m_tot flops, 12 B/edge + 8 B/vertex.
      init    — label propagation on the coarsest graph (m_c ≈ m/2^(L−1)),
                ~8 sweeps across restarts.
      refine  — ``rounds`` engine rounds per level; each scores every edge
                (segment-sum or scoreboard: ≈2 flops/edge) and argmaxes an
                (n, k) connectivity row.

    These are *useful-work floors*, not fitted costs: dividing by measured
    wall time gives an achieved-vs-peak fraction that is ≤ the true
    hardware utilisation, which is exactly the conservative direction a
    roofline gate wants."""
    n_tot, m_tot = 2.0 * n, 2.0 * m
    shrink = 2 ** max(int(levels) - 1, 0)
    n_c, m_c = max(n / shrink, 1.0), max(m / shrink, 1.0)
    r = float(rounds)
    return {
        "coarsen": {
            "flops": 4.0 * m_tot,
            "bytes": 12.0 * m_tot + 8.0 * n_tot,
        },
        "init": {
            "flops": 8.0 * (m_c + n_c * k),
            "bytes": 8.0 * (4.0 * m_c + 4.0 * n_c * k),
        },
        "refine": {
            "flops": r * (2.0 * m_tot + n_tot * k),
            "bytes": r * (8.0 * m_tot + 4.0 * n_tot * k),
        },
    }


def phase_roofline(flops: float, nbytes: float, seconds: float,
                   hw=None) -> dict[str, float]:
    """Achieved-vs-peak fractions for one timed phase: useful flops and
    bytes (e.g. from :func:`partition_phase_model`) over measured seconds,
    against the resolved hardware's peaks."""
    hw = resolve_hw(hw)
    s = max(float(seconds), 1e-12)
    return {
        "flops": float(flops),
        "bytes": float(nbytes),
        "flops_frac": (float(flops) / s) / hw["peak_flops"],
        "bw_frac": (float(nbytes) / s) / hw["hbm_bw"],
    }


def model_flops_for(cfg, shape) -> float:
    """Analytic MODEL_FLOPS: 6·N·D train / 2·N_active per decoded token."""
    n_active = cfg.active_param_count()
    tokens = shape.global_batch * (shape.seq_len if shape.mode != "decode" else 1)
    if shape.mode == "train":
        return 6.0 * n_active * tokens
    return 2.0 * n_active * tokens


def scan_flops_correction(cfg, shape) -> float:
    """Global FLOPs missed by cost_analysis inside *inner* sequence scans.

    With the layer stack unrolled, the remaining while-loops are the
    blockwise-attention / SSD-chunk / xLSTM scans whose bodies XLA counts
    once; this adds the analytic (trip−1)/trip remainder.  Train multiplies
    the forward count by 4 (recompute-under-remat + 2× backward); prefill
    counts forward only; decode paths contain no inner scans (→ 0).
    """
    if shape.mode == "decode":
        return 0.0
    B, S = shape.global_batch, shape.seq_len
    mult = 4.0 if shape.mode == "train" else 1.0
    total = 0.0
    hd = cfg.head_dim
    for lt in cfg.layer_types:
        if lt in ("dense", "moe", "attn"):
            if cfg.attn_type == "mla":
                dqk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
                per = 4.0 * B * S * S * cfg.n_heads * dqk
            else:
                per = 4.0 * B * S * S * cfg.n_heads * hd
            chunk = 512  # attention.blockwise_attention default
            trips = max(S // chunk, 1)
            total += per * (trips - 1) / trips * mult
        elif lt == "mamba2":
            C = min(cfg.ssm_chunk, S)
            nh = (cfg.ssm_expand * cfg.d_model) // cfg.ssm_head_dim
            P_ = cfg.ssm_head_dim
            N = cfg.ssm_state
            per = (2.0 * B * S * C * nh * P_        # intra-chunk y
                   + 2.0 * B * S * C * N            # scores
                   + 6.0 * B * S * N * nh * P_)     # inter/carry terms
            trips = max(S // C, 1)
            total += per * (trips - 1) / trips * mult
        elif lt == "mlstm":
            C = 64
            H = cfg.n_heads
            hd_ = cfg.d_model // H
            per = (4.0 * B * S * C * H * hd_ + 4.0 * B * S * H * hd_ * hd_)
            trips = max(S // C, 1)
            total += per * (trips - 1) / trips * mult
        elif lt == "slstm":
            H = cfg.n_heads
            hd_ = cfg.d_model // H
            per = 8.0 * B * S * H * hd_ * hd_
            total += per * (S - 1) / S * mult
        elif lt == "xattn":
            per = 4.0 * B * S * cfg.n_vision_tokens * cfg.n_heads * hd
            total += 0.0 * per  # not scanned — already counted
    return total
