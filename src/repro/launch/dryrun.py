import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("REPRO_XLA_EXTRA", "")
)

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The two lines above MUST run before any jax import (device count locks at
first init).  This module is the ONLY place that forces 512 host devices;
tests and benchmarks see the real single CPU device.

Per cell:
  * build abstract params/optimizer/cache (eval_shape — no allocation),
  * jit(train_step | forward | serve_step) with the sharding rules,
  * .lower(...).compile()  → memory_analysis() proves the per-device
    footprint, cost_analysis() + HLO collective parse feed §Roofline.

Usage:
  python -m repro.launch.dryrun --arch starcoder2_15b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both --out results/dryrun
  python -m repro.launch.dryrun --partitioner            # paper-side dry-run
"""

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.sharding.compat import shard_map as compat_shard_map

from repro import configs
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import make_cell
from repro.optim.api import make_optimizer
from repro.roofline.analysis import (
    analyze_compiled,
    model_flops_for,
    scan_flops_correction,
)

# the compiled cells run on forced CPU devices and model this chip's peaks
TARGET_HW = "v5e"

# per-arch launcher policy: optimizer + memory knobs for the big models
ARCH_POLICY = {
    "deepseek_v3_671b": dict(optimizer="adafactor", zero_over_pod=True),
    "llama3_2_vision_90b": dict(optimizer="adamw", moment_dtype="bf16",
                                zero_over_pod=True),
}


def _optimizer_for(arch: str):
    pol = ARCH_POLICY.get(arch, {})
    return make_optimizer(
        pol.get("optimizer", "adamw"),
        lr=1e-4,
        moment_dtype=pol.get("moment_dtype", "f32"),
    ), pol.get("zero_over_pod", False)


def analytic_memory(cfg, shape, mesh, zero_over_pod: bool) -> dict:
    """Per-device memory model (bytes) for the TPU target: params (bf16) +
    optimizer state + transient grads + checkpointed activations / caches.
    The XLA CPU backend's temp_size is reported alongside but its buffer
    assignment is not the TPU one."""
    n = cfg.param_count()
    n_chips = mesh.devices.size
    dp = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)
    fsdp = mesh.shape.get("data", 1) * (mesh.shape.get("pod", 1) if zero_over_pod else 1)
    tp = mesh.shape.get("model", 1)
    shard = fsdp * tp  # most weights shard over both axes
    pol = ARCH_POLICY.get(cfg.name, {})
    opt_bpp = {"adafactor": 4.05, "bf16": 8.0, "int8": 6.0}.get(
        pol.get("optimizer", pol.get("moment_dtype", "f32")), 12.0)
    params_b = 2.0 * n / shard
    if shape.mode == "train":
        opt_b = opt_bpp * n / shard
        grads_b = 4.0 * n / shard
        b_loc = max(shape.global_batch // dp, 1)
        act_b = cfg.n_layers * b_loc * shape.seq_len * cfg.d_model * 2.0 / tp
        cache_b = 0.0
    else:
        opt_b = grads_b = 0.0
        b_loc = max(shape.global_batch // dp, 1)
        act_b = b_loc * shape.seq_len * cfg.d_model * 2.0
        cache_b = 0.0
        if shape.mode == "decode":
            act_b = b_loc * cfg.d_model * 2.0
            per_pos = 0.0
            for lt in cfg.layer_types:
                if lt in ("dense", "moe", "attn"):
                    if cfg.attn_type == "mla":
                        per_pos += cfg.kv_lora_rank + cfg.qk_rope_head_dim
                    else:
                        kv_shard = tp if cfg.n_kv_heads % tp == 0 else 1
                        per_pos += 2 * cfg.n_kv_heads * cfg.head_dim / kv_shard
            cache_b = per_pos * shape.seq_len * b_loc * 2.0
            for lt in cfg.layer_types:  # ssm states
                if lt == "mamba2":
                    d_in = cfg.ssm_expand * cfg.d_model
                    nh = d_in // cfg.ssm_head_dim
                    nh_shard = tp if nh % tp == 0 else 1
                    cache_b += b_loc * nh * cfg.ssm_state * cfg.ssm_head_dim * 4.0 / nh_shard
                elif lt in ("mlstm", "slstm"):
                    hd = cfg.d_model // cfg.n_heads
                    cache_b += b_loc * cfg.n_heads * hd * (hd + 3) * 4.0
    total = params_b + opt_b + grads_b + act_b + cache_b
    return {
        "params_b": params_b, "opt_b": opt_b, "grads_b": grads_b,
        "act_b": act_b, "cache_b": cache_b, "total_b": total,
        "fits_16g": bool(total < 16e9),
    }


def analytic_hbm_bytes(cfg, shape, mesh, zero_over_pod: bool) -> float:
    """Expected per-device HBM traffic per step (bytes) — the roofline memory
    term.  XLA's cost_analysis 'bytes accessed' sums per-instruction operand
    bytes pre-fusion (a big over-count); this model counts what actually
    moves: weights (fwd + bwd + remat reads), grads (write+read), optimizer
    state (read+write), activation checkpoints, and decode caches."""
    mem = analytic_memory(cfg, shape, mesh, zero_over_pod)
    if shape.mode == "train":
        w_traffic = 3.0 * mem["params_b"]            # fwd + remat + bwd reads
        g_traffic = 2.0 * mem["grads_b"]
        o_traffic = 2.0 * mem["opt_b"]
        act_traffic = 8.0 * mem["act_b"]             # save+3 reads+recompute
        dp = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)
        b_loc = max(shape.global_batch // dp, 1)
        tp = mesh.shape.get("model", 1)
        v_shard = tp if cfg.vocab_size % tp == 0 else 1
        logits_traffic = 6.0 * b_loc * shape.seq_len * cfg.vocab_size / v_shard * 2.0
        return w_traffic + g_traffic + o_traffic + act_traffic + logits_traffic
    if shape.mode == "prefill":
        return 2.0 * mem["params_b"] + 6.0 * mem["act_b"]
    # decode: weights once, cache read+write
    return mem["params_b"] + 2.0 * mem["cache_b"]


def run_cell(arch: str, shape_name: str, multi_pod: bool, lower_only: bool = False,
             variant: str = "baseline"):
    """Lower + compile one cell; returns a result dict (or skip record)."""
    shape = configs.SHAPES[shape_name]
    ok, reason = configs.shape_applicable(arch, shape_name)
    rec = {
        "arch": arch if variant == "baseline" else f"{arch}+{variant}",
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "variant": variant,
    }
    if not ok:
        rec.update(status="skipped", reason=reason)
        return rec

    cfg = configs.get(arch)
    if arch == "zamba2_7b" and shape_name == "long_500k":
        import dataclasses
        cfg = dataclasses.replace(cfg, attn_window=8192)

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    optimizer, zop = _optimizer_for(arch)

    t0 = time.time()
    # REPRO_UNROLL=0 keeps the layer scan (fast compile) — used for the
    # multi-pod shard-coherence pass; the single-pod roofline pass unrolls.
    unroll = os.environ.get("REPRO_UNROLL", "1") != "0"
    fn, args, in_shardings = make_cell(cfg, shape, mesh, optimizer,
                                       zero_over_pod=zop, variant=variant,
                                       unroll_layers=unroll)
    from jax.sharding import NamedSharding, PartitionSpec as P

    in_shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), in_shardings,
        is_leaf=lambda x: isinstance(x, P),
    )
    with mesh:
        jitted = jax.jit(fn, in_shardings=in_shardings)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        if lower_only:
            rec.update(status="lowered", lower_s=round(t_lower, 1),
                       analytic_mem=analytic_memory(cfg, shape, mesh, zop))
            return rec
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mf = model_flops_for(cfg, shape)
    if unroll:
        body_scale = 1.0
    else:
        # scan-mode: collectives inside while bodies execute per repeat but
        # appear once in the text — scale by the dominant segment's repeats
        from repro.models.transformer import segments as _segments

        body_scale = float(max(r for _, r in _segments(cfg)))
    roof = analyze_compiled(compiled, n_chips, mf, hw=TARGET_HW,
                            body_scale=body_scale)
    # inner-scan flop remainder (analytic, global → per-device)
    corr = scan_flops_correction(cfg, shape) / n_chips
    if not unroll:
        # layer-stack flops also counted once in scan mode: approximate with
        # MODEL_FLOPS-based analytic (remat factor 4/3 train, 1 otherwise)
        remat = (4.0 / 3.0) if shape.mode == "train" else 1.0
        roof.flops = max(roof.flops, mf * remat / n_chips)
    roof.flops += corr
    roof.compute_s = roof.flops / 197e12
    hbm_analytic = analytic_hbm_bytes(cfg, shape, mesh, zop)
    memory_s_analytic = hbm_analytic / 819e9
    terms = {"compute": roof.compute_s, "memory": memory_s_analytic,
             "collective": roof.collective_s}
    roof.bottleneck = max(terms, key=terms.get)
    roof.useful_ratio = mf / (roof.flops * n_chips) if roof.flops else 0.0
    print(compiled.memory_analysis())
    print({k: v for k, v in compiled.cost_analysis().items()
           if k in ("flops", "bytes accessed")})

    rec.update(
        status="ok",
        lower_s=round(t_lower, 1),
        compile_s=round(t_compile, 1),
        accounting="unrolled" if unroll else "scan-scaled",
        n_params=cfg.param_count(),
        n_active_params=cfg.active_param_count(),
        scan_flops_corr_per_device=corr,
        flops_per_device=roof.flops,
        hbm_bytes_per_device=roof.hbm_bytes,
        coll_bytes=roof.coll_bytes,
        compute_s=roof.compute_s,
        memory_s=memory_s_analytic,
        memory_s_xla_upper=roof.memory_s,
        hbm_bytes_analytic=hbm_analytic,
        collective_s=roof.collective_s,
        bottleneck=roof.bottleneck,
        model_flops=mf,
        useful_ratio=roof.useful_ratio,
        mem_per_device=roof.mem_per_device,
        analytic_mem=analytic_memory(cfg, shape, mesh, zop),
    )
    return rec


# --------------------------------------------------------------------------
# paper-side dry-run: distributed Jet round + rebalance on the full mesh
# --------------------------------------------------------------------------

def run_partitioner_cell(multi_pod: bool, n_local: int = 1 << 18,
                         deg: int = 16, k: int = 128, halo: bool = False,
                         halo_frac: float = 0.1):
    """Lower+compile one distributed Jet iteration (round + probabilistic
    rebalance pass) with P = mesh-size PEs, n_local vertices and deg·n_local
    edge slots per PE — the shape of the paper's weak-scaling experiment
    (Fig. 2a).  ``halo=True`` runs the interface-only exchange variant
    (§Perf hillclimb #1) with h_local = halo_frac·n_local interface vertices
    (meshy surface/volume regime)."""
    from jax.sharding import PartitionSpec as P

    from repro.sharding.compat import make_mesh_from_devices

    mesh = make_production_mesh(multi_pod=multi_pod)
    devs = mesh.devices.reshape(-1)
    pe_mesh = make_mesh_from_devices(devs, ("pe",))
    Pn = devs.size
    m_local = n_local * deg

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt)

    if halo:
        from repro.distributed.halo import (
            HaloShardedGraph,
            halo_jet_round_local,
            halo_prob_pass_local,
        )

        h_local = max(1, int(n_local * halo_frac))

        def per_pe(sg, labels, locked, key, lmax):
            lab, _ = halo_jet_round_local(sg, labels[0], locked[0],
                                          jnp.float32(0.5), k=k)
            lab = halo_prob_pass_local(sg, lab, key, lmax, k=k)
            return lab[None]

        sh = P("pe", None)
        sg_specs = HaloShardedGraph(
            src=sh, dst_code=sh, head_gid=sh, ew=sh, nw=sh, my_gid=sh,
            owned=sh, perm_loc=sh, inv_perm=sh, gstart=P("pe"),
            n_real=Pn * n_local, P=Pn, n_local=n_local,
            m_local=m_local, h_local=h_local,
        )
        f = jax.jit(compat_shard_map(
            per_pe, mesh=pe_mesh,
            in_specs=(sg_specs, sh, sh, P(), P()),
            out_specs=sh,
        ))
        sg_args = HaloShardedGraph(
            src=s((Pn, m_local), jnp.int32), dst_code=s((Pn, m_local), jnp.int32),
            head_gid=s((Pn, m_local), jnp.int32), ew=s((Pn, m_local), jnp.float32),
            nw=s((Pn, n_local), jnp.float32), my_gid=s((Pn, n_local), jnp.int32),
            owned=s((Pn, n_local), jnp.bool_),
            perm_loc=s((Pn, n_local), jnp.int32),
            inv_perm=s((Pn, n_local), jnp.int32), gstart=s((Pn,), jnp.int32),
            n_real=Pn * n_local, P=Pn,
            n_local=n_local, m_local=m_local, h_local=h_local,
        )
        args = (sg_args, s((Pn, n_local), jnp.int32), s((Pn, n_local), jnp.bool_),
                s((2,), jnp.uint32), s((), jnp.float32))
    else:
        from repro.distributed.djet import djet_round_local, dprob_pass_local

        n_real = Pn * n_local

        def per_pe(src, dst, ew, nw, owned, labels, locked, gstart, key, lmax):
            lab, moved = djet_round_local(src[0], dst[0], ew[0], nw[0], owned[0],
                                          labels[0], locked[0], jnp.float32(0.5),
                                          k=k, n_local=n_local)
            lab = dprob_pass_local(src[0], dst[0], ew[0], nw[0], owned[0],
                                   lab, gstart[0], key, lmax,
                                   k=k, n_local=n_local, n_real=n_real)
            return lab[None]

        sh = P("pe", None)
        f = jax.jit(compat_shard_map(
            per_pe, mesh=pe_mesh,
            in_specs=(sh, sh, sh, sh, sh, sh, sh, P("pe"), P(), P()),
            out_specs=sh,
        ))
        args = (
            s((Pn, m_local), jnp.int32), s((Pn, m_local), jnp.int32),
            s((Pn, m_local), jnp.float32), s((Pn, n_local), jnp.float32),
            s((Pn, n_local), jnp.bool_), s((Pn, n_local), jnp.int32),
            s((Pn, n_local), jnp.bool_), s((Pn,), jnp.int32),
            s((2,), jnp.uint32), s((), jnp.float32),
        )

    t0 = time.time()
    with pe_mesh:
        lowered = f.lower(*args)
        compiled = lowered.compile()
    roof = analyze_compiled(compiled, Pn, model_flops=0.0, hw=TARGET_HW)
    print(compiled.memory_analysis())
    name = "paper_partitioner_jet" + ("+halo" if halo else "")
    return {
        "arch": name, "shape": f"n_local={n_local},deg={deg},k={k}",
        "mesh": "2x16x16" if multi_pod else "16x16",
        "status": "ok", "compile_s": round(time.time() - t0, 1),
        "flops_per_device": roof.flops,
        "hbm_bytes_per_device": roof.hbm_bytes,
        "coll_bytes": roof.coll_bytes,
        "compute_s": roof.compute_s, "memory_s": roof.memory_s,
        "collective_s": roof.collective_s, "bottleneck": roof.bottleneck,
        "mem_per_device": roof.mem_per_device,
    }


def run_ring_decode_cell(multi_pod: bool = False):
    """§Perf cell 3 iteration 2: one layer of context-parallel decode
    attention at the starcoder2 decode_32k geometry.  Collective bytes here
    × 40 layers is the projected per-step attention collective."""
    from jax.sharding import PartitionSpec as P

    from repro.models.ring_decode import ring_cache_update, ring_decode_attention_local

    mesh = make_production_mesh(multi_pod=multi_pod)
    B, S, Hq, Hkv, hd = 128, 32_768, 48, 4, 128
    dp = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)
    tp = mesh.shape["model"]
    groups = Hq // Hkv

    def per_shard(q, k_loc, v_loc, k_new, v_new, pos):
        k_loc, v_loc = ring_cache_update(k_loc, v_loc, k_new, v_new, pos)
        o = ring_decode_attention_local(q, k_loc, v_loc, pos, groups)
        return o, k_loc, v_loc

    bspec = ("pod", "data") if "pod" in mesh.shape else ("data",)
    cache_spec = P(bspec, "model", None, None)
    f = jax.jit(compat_shard_map(
        per_shard, mesh=mesh,
        in_specs=(P(bspec), cache_spec, cache_spec, P(bspec), P(bspec), P()),
        out_specs=(P(bspec), cache_spec, cache_spec),
    ))

    def s(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt)

    args = (s((B, Hq, hd)), s((B, S, Hkv, hd)), s((B, S, Hkv, hd)),
            s((B, 1, Hkv, hd)), s((B, 1, Hkv, hd)),
            jax.ShapeDtypeStruct((), jnp.int32))
    t0 = time.time()
    with mesh:
        compiled = f.lower(*args).compile()
    roof = analyze_compiled(compiled, mesh.devices.size, model_flops=0.0,
                            hw=TARGET_HW)
    n_layers = 40
    per_layer = sum(roof.coll_bytes.values())
    rec = {
        "arch": "starcoder2_15b+ringdecode(1layer)", "shape": "decode_32k",
        "mesh": "2x16x16" if multi_pod else "16x16", "status": "ok",
        "compile_s": round(time.time() - t0, 1),
        "coll_bytes": roof.coll_bytes,
        "coll_bytes_per_layer": per_layer,
        "collective_s_40layers": per_layer * n_layers / 50e9,
        "memory_s": roof.memory_s,
        "bottleneck": "memory",
    }
    print(json.dumps(rec))
    return rec


def run_moe_ep_cell(multi_pod: bool = False, capacity_factor: float = 1.25):
    """§Perf follow-up to the deepseek-v3 finding: one MoE layer with the
    explicit shard_map expert-parallel all-to-all (models/moe_ep.py) at the
    train_4k geometry.  a2a bytes here × 58 layers × 3 (fwd + 2×bwd) is the
    projected per-step MoE collective — vs the 93 TB GSPMD fallback."""
    from jax.sharding import PartitionSpec as P

    from repro import configs
    from repro.models.moe_ep import moe_ep_local

    cfg = configs.get("deepseek_v3_671b")
    mesh = make_production_mesh(multi_pod=multi_pod)
    dp = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)
    tp = mesh.shape["model"]
    n_chips = mesh.devices.size
    shape = configs.SHAPES["train_4k"]
    t_loc = shape.global_batch * shape.seq_len // n_chips  # tokens per device
    d, fdim = cfg.d_model, cfg.d_expert
    E_local = cfg.n_experts // tp

    def per_shard(router, wg, wu, wd, x_loc):
        p_local = {"router": router, "w_gate": wg, "w_up": wu, "w_down": wd}
        return moe_ep_local(p_local, x_loc, cfg, capacity_factor=capacity_factor)

    bspec = ("pod", "data") if "pod" in mesh.shape else ("data",)
    f = jax.jit(compat_shard_map(
        per_shard, mesh=mesh,
        in_specs=(P(), P("model", None, None), P("model", None, None),
                  P("model", None, None), P((*bspec, "model"), None)),
        out_specs=P((*bspec, "model"), None),
    ))

    def s(shp, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shp, dt)

    args = (s((d, cfg.n_experts), jnp.float32),
            s((cfg.n_experts, d, fdim)), s((cfg.n_experts, d, fdim)),
            s((cfg.n_experts, fdim, d)),
            s((t_loc * n_chips, d)))
    t0 = time.time()
    with mesh:
        compiled = f.lower(*args).compile()
    roof = analyze_compiled(compiled, n_chips, model_flops=0.0, hw=TARGET_HW)
    n_moe_layers = cfg.n_layers - cfg.n_dense_layers
    per_layer = sum(roof.coll_bytes.values())
    step_coll = per_layer * n_moe_layers * 3.0  # fwd + ~2x bwd
    rec = {
        "arch": "deepseek_v3_671b+ep_a2a(1layer)", "shape": "train_4k",
        "mesh": "2x16x16" if multi_pod else "16x16", "status": "ok",
        "compile_s": round(time.time() - t0, 1),
        "capacity_factor": capacity_factor,
        "coll_bytes": roof.coll_bytes,
        "coll_bytes_per_layer": per_layer,
        "projected_step_collective_s": step_coll / 50e9,
        "bottleneck": "collective",
    }
    print(json.dumps(rec))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=("single", "multi", "both"), default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--partitioner", action="store_true")
    ap.add_argument("--halo", action="store_true",
                    help="partitioner cell with interface-only halo exchange")
    ap.add_argument("--ring-decode", action="store_true",
                    help="context-parallel decode attention measurement")
    ap.add_argument("--moe-ep", action="store_true",
                    help="expert-parallel all-to-all MoE layer measurement")
    ap.add_argument("--variant", default="baseline",
                    choices=("baseline", "fsdp", "seqkv"),
                    help="LM-cell §Perf variant")
    ap.add_argument("--lower-only", action="store_true",
                    help="stop after .lower() (fast shard-coherence sweep)")
    ap.add_argument("--out", default=None, help="directory for per-cell JSON")
    args = ap.parse_args()

    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    results = []

    if args.moe_ep:
        for mp in meshes:
            results.append(run_moe_ep_cell(mp))
    elif args.ring_decode:
        for mp in meshes:
            results.append(run_ring_decode_cell(mp))
    elif args.partitioner:
        for mp in meshes:
            results.append(run_partitioner_cell(mp, halo=args.halo))
    else:
        cells = (
            list(configs.all_cells())
            if args.all
            else [(configs.canon(args.arch), args.shape)]
        )
        for arch, shape in cells:
            for mp in meshes:
                try:
                    rec = run_cell(arch, shape, mp, lower_only=args.lower_only,
                                   variant=args.variant)
                except Exception as e:  # a failing cell is a bug — surface it
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "2x16x16" if mp else "16x16",
                           "status": "error", "error": f"{type(e).__name__}: {e}",
                           "trace": traceback.format_exc()[-2000:]}
                results.append(rec)
                print(json.dumps({k: v for k, v in rec.items() if k != "trace"}))

    if args.out:
        os.makedirs(args.out, exist_ok=True)
        for rec in results:
            name = f"{rec['arch']}__{rec['shape']}__{rec['mesh']}.json".replace("/", "_")
            with open(os.path.join(args.out, name), "w") as f:
                json.dump(rec, f, indent=1)

    bad = [r for r in results if r.get("status") == "error"]
    print(f"[dryrun] {len(results)} cells, {len(bad)} errors")
    return 1 if bad else 0


if __name__ == "__main__":
    raise SystemExit(main())
