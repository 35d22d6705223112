"""Partitioner CLI — the paper-side launcher.

    PYTHONPATH=src python -m repro.launch.partition --graph rmat_14 --k 16 \
        --refiner d4xjet [--distributed P]
"""

import argparse
import json
import os
import time

from repro.core import PartitionConfig, partition
from repro.graphs import BENCHMARK_SET, generate
from repro.launch.compile_cache import enable_compile_cache
from repro.refine.schedule import SCHEDULE_ALIASES, SCHEDULES, resolve_schedule
from repro.refine.variants import ALIASES, registered_variants


def _force_host_devices(p: int) -> None:
    """On the CPU platform, simulate ``p`` PEs with forced host devices:
    appends the flag to ``XLA_FLAGS`` (keeping the caller's flags, and a
    device count the caller already forced).  Must run before the backend
    initialises; an accelerator platform keeps its real devices."""
    if os.environ.get("JAX_PLATFORMS", "").split(",")[0] != "cpu":
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={p}".strip())


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--graph", default="grid2d_64k", choices=sorted(BENCHMARK_SET))
    ap.add_argument("--k", type=int, default=16)
    ap.add_argument("--eps", type=float, default=0.03)
    ap.add_argument("--refiner", default="d4xjet",
                    choices=sorted((*registered_variants(), *ALIASES)))
    ap.add_argument("--schedule", default="constant",
                    choices=sorted((*SCHEDULES, *SCHEDULE_ALIASES)),
                    help="per-level imbalance-tolerance schedule "
                         "(repro.refine.schedule)")
    ap.add_argument("--eps-coarse", type=float, default=None,
                    help="coarsest-level tolerance of the geometric schedule")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--coarsen-until", type=int, default=None,
                    help="stop coarsening at this many vertices "
                         "(default: max(512, 16k))")
    ap.add_argument("--distributed", type=int, default=0,
                    help="run the sharded V-cycle over P devices (on the "
                         "CPU platform: P forced host devices)")
    ap.add_argument("--ingest", default=None, metavar="MANIFEST",
                    help="out-of-core input: build the device shards from a "
                         "chunked edge manifest (repro.graphs.ingest) instead "
                         "of generating --graph centrally; requires "
                         "--distributed P")
    ap.add_argument("--ckpt-dir", default=None,
                    help="snapshot the V-cycle into this directory after "
                         "initial partitioning and each refinement rung "
                         "(repro.checkpoint.CheckpointPolicy)")
    ap.add_argument("--ckpt-every", type=int, default=1,
                    help="snapshot cadence in refinement rungs (default 1)")
    ap.add_argument("--resume", action="store_true",
                    help="restore the latest committed snapshot from "
                         "--ckpt-dir and continue; bit-identical to the "
                         "uninterrupted run, including under a different "
                         "--distributed P (elastic resume)")
    ap.add_argument("--labels-out", default=None, metavar="PATH",
                    help="np.save the final (n,) int32 label array here")
    ap.add_argument("--halo", action="store_true",
                    help="interface-only halo exchange (distributed fast path)")
    ap.add_argument("--batch", type=int, default=0,
                    help="partition BATCH copies of --graph in one "
                         "request-batched call (core.partition_batch; "
                         "B=1 is bit-identical to the solo path)")
    ap.add_argument("--serve-trace", default=None, metavar="KIND:N:MEAN_GAP_US",
                    help="serve an N-request arrival trace of --graph through "
                         "the stream scheduler (repro.serve) instead of one "
                         "call; KIND is poisson or burst, MEAN_GAP_US the "
                         "mean inter-arrival gap (virtual microseconds). "
                         "Seeds cycle 0..7 so the buffer pool's plan/init "
                         "caches engage. Example: poisson:64:200")
    ap.add_argument("--serve-batch", type=int, default=8,
                    help="scheduler flush size target (FlushPolicy.batch_target)")
    ap.add_argument("--serve-deadline-us", type=float, default=None,
                    help="oldest-request flush deadline in virtual "
                         "microseconds (default: size-only flushing)")
    ap.add_argument("--serve-mode", default="stream",
                    choices=("stream", "replay", "wallclock"),
                    help="front for --serve-trace: 'stream' is the "
                         "synchronous batch replay (partition_stream); "
                         "'replay' submits the trace to the async "
                         "PartitionService under the virtual clock "
                         "(bit-identical to stream); 'wallclock' paces "
                         "submissions in real time and enforces "
                         "--serve-deadline-us against monotonic time")
    args = ap.parse_args()
    if args.distributed:
        _force_host_devices(args.distributed)
    enable_compile_cache()
    if sum(map(bool, (args.batch, args.distributed,
                      args.serve_trace))) > 1:
        ap.error("--batch, --distributed and --serve-trace are "
                 "mutually exclusive")
    if args.ingest and not args.distributed:
        ap.error("--ingest needs --distributed P (the shards are built for "
                 "P devices; the centralised paths would gather them back)")
    if args.resume and not args.ckpt_dir:
        ap.error("--resume restores from --ckpt-dir; pass both")
    if args.ckpt_dir and (args.batch or args.serve_trace):
        ap.error("--ckpt-dir applies to the solo and --distributed paths; "
                 "the batched/serving engines reject checkpointing")
    # canonicalize aliases (unconstrained-then-snap → snap): the string is
    # echoed in the output JSON, where it keys cross-run comparisons
    args.schedule = resolve_schedule(args.schedule).mode

    policy = None
    if args.ckpt_dir:
        from repro.checkpoint import CheckpointPolicy

        policy = CheckpointPolicy(ckpt_dir=args.ckpt_dir,
                                  every_levels=args.ckpt_every)
    cfg = PartitionConfig(k=args.k, eps=args.eps, refiner=args.refiner,
                          schedule=args.schedule, eps_coarse=args.eps_coarse,
                          coarsen_until=args.coarsen_until, ckpt=policy)
    resume_dir = args.ckpt_dir if args.resume else None

    if args.serve_trace:
        import numpy as np

        from repro.serve import (
            BufferPool,
            FlushPolicy,
            PartitionRequest,
            PartitionService,
            partition_stream,
        )

        try:
            kind, n_req, gap = args.serve_trace.split(":")
            n_req, gap = int(n_req), float(gap)
            if kind not in ("poisson", "burst") or n_req < 1 or gap < 0:
                raise ValueError
        except ValueError:
            ap.error("--serve-trace wants KIND:N:MEAN_GAP_US with KIND in "
                     "{poisson, burst}, N >= 1, MEAN_GAP_US >= 0 "
                     f"(got {args.serve_trace!r})")
        rng = np.random.RandomState(args.seed)
        gaps = rng.exponential(gap, size=n_req)
        if kind == "burst":  # groups of 4 back-to-back, 4x gaps between
            gaps = np.where(np.arange(n_req) % 4 == 0, gaps * 4.0, 0.0)
        t_uss = np.cumsum(gaps)

        g = generate(args.graph)
        n_out, m_out = g.n, g.m
        reqs = [PartitionRequest(g, config=cfg, seed=i % 8, t_us=float(t))
                for i, t in enumerate(t_uss)]
        policy = FlushPolicy(batch_target=args.serve_batch,
                             deadline_us=args.serve_deadline_us)
        pool = BufferPool()
        t0 = time.time()
        if args.serve_mode == "stream":
            results, log = partition_stream(reqs, policy=policy, pool=pool,
                                            report=True)
            sec = time.time() - t0
            reasons: dict = {}
            for fl in log:
                reasons[fl["reason"]] = reasons.get(fl["reason"], 0) + 1
            extra = dict(flushes=len(log), flush_reasons=reasons)
        else:
            with PartitionService(policy=policy, pool=pool,
                                  mode=args.serve_mode) as svc:
                if args.serve_mode == "wallclock":
                    futs, prev = [], 0.0
                    for r in reqs:  # pace arrivals against the real clock
                        time.sleep(max(0.0, (r.t_us - prev) / 1e6))
                        prev = r.t_us
                        futs.append(svc.submit(r.graph, config=r.config,
                                               seed=r.seed))
                else:
                    futs = [svc.submit_request(r) for r in reqs]
            # results AFTER the with block: __exit__ drains pending buckets
            # (a size-only tail bucket, or a replay-mode deadline bucket with
            # no later arrival to expire it, only flushes at drain — calling
            # result() inside the block would deadlock on that tail)
            results = [f.result() for f in futs]
            sec = time.time() - t0
            stats = svc.stats()
            stats.pop("pool", None)  # printed separately below
            extra = dict(service=stats)
        res = results[0]
        out = dict(cut=res.cut, imbalance=res.imbalance, levels=res.levels,
                   trace=kind, front=args.serve_mode, requests=n_req,
                   serve_batch=args.serve_batch,
                   pool=pool.stats(), sec=round(sec, 2),
                   graphs_per_sec=round(n_req / sec, 3), **extra)
    elif args.batch:
        from repro.core import partition_batch

        g = generate(args.graph)
        n_out, m_out = g.n, g.m
        t0 = time.time()
        results = partition_batch([g] * args.batch, seed=args.seed,
                                  config=cfg)
        sec = time.time() - t0
        res = results[0]  # identical graphs + one seed → identical slots
        out = dict(cut=res.cut, imbalance=res.imbalance, levels=res.levels,
                   batch=args.batch, sec=round(sec, 2),
                   graphs_per_sec=round(args.batch / sec, 3))
    elif args.distributed:
        import jax

        from repro.distributed import dpartition

        if args.distributed > jax.device_count():
            ap.error(f"--distributed {args.distributed} needs that many "
                     f"devices; this {jax.default_backend()} backend has "
                     f"{jax.device_count()}")

        if args.ingest:
            from repro.graphs import ingest_sharded, load_manifest

            man = load_manifest(args.ingest)
            g = ingest_sharded(man, P=args.distributed)
            n_out, m_out = man["n"], man["m"]
        else:
            g = generate(args.graph)
            n_out, m_out = g.n, g.m
        t0 = time.time()
        res = dpartition(g, P=args.distributed, seed=args.seed,
                         halo=args.halo, resume=resume_dir, config=cfg)
        out = dict(cut=res.cut, imbalance=res.imbalance, levels=res.levels,
                   P=res.P, sec=round(time.time() - t0, 2))
    else:
        g = generate(args.graph)
        n_out, m_out = g.n, g.m
        t0 = time.time()
        res = partition(g, seed=args.seed, resume=resume_dir, config=cfg)
        out = dict(cut=res.cut, imbalance=res.imbalance, levels=res.levels,
                   sec=round(time.time() - t0, 2))
    if args.ckpt_dir:
        out.update(resumed_from=res.resume_step)
    if args.labels_out:
        import numpy as np

        np.save(args.labels_out, np.asarray(res.labels, dtype=np.int32))
        out.update(labels_out=args.labels_out)
    out.update(graph=args.ingest or args.graph, n=n_out, m=m_out, k=args.k,
               refiner=args.refiner, schedule=args.schedule,
               level_eps=[round(e, 6) for e in res.level_eps])
    print(json.dumps(out))


if __name__ == "__main__":
    main()
