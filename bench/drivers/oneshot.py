"""One-shot jobs back to back on one loaded graph, as a user repartitions a
graph they have loaded.

Every seed gets the same work: the configuration's graph drawn from
generator seed 0 and the partitioner seeded with 0.  ``--seed`` orders
the edge list handed to the program, which puts it in canonical order.
(The partitioner's seed changes its work, and every new graph or seed
compiles new level programs: see PERF.md.)

A window job is one call of the program's ``dpartition`` and ends when
the labels are on the host.  A job that starts before ``seconds`` have
elapsed runs to its end and counts.  The mix takes no parameters.
"""

from __future__ import annotations

import sys
import time

import numpy as np

from bench import graphs

# numpy's generators take a non-negative whole number
SEED_MOD = 1 << 64


def job(g, part: dict, seed: int, timing: bool) -> dict:
    """One call of the program's ``dpartition``; ends with the labels on
    the host."""
    from repro.distributed import dpartition

    res = dpartition(g, k=part["k"], P=part["P"], eps=part["eps"],
                     seed=seed, refiner=part["refiner"], gain=part["gain"],
                     coarsen=part["coarsen"], halo=part["halo"],
                     timing=timing)
    return {"labels": np.asarray(res.labels), "cut": res.cut,
            "imbalance": res.imbalance, "timings": res.timings,
            "level_backends": list(res.level_backends)}


def load_graph(config: dict, graph_seed: int, order_seed: int):
    """``(program Graph, check graph)`` of one draw: the program's through
    its public ``from_coo``, with the edges in an order drawn from
    ``order_seed``; the check's through ``graphs.edge_list``."""
    from repro.core.graph import from_coo

    n, u, v = graphs.generate(config["graph"], graph_seed % SEED_MOD)
    order = np.random.default_rng(order_seed % SEED_MOD).permutation(
        len(u))
    u, v = u[order], v[order]
    return from_coo(n, u, v), (n, *graphs.edge_list(u, v))


def answer(g, graph, part, seed, job, timing, t0: float) -> dict:
    """One job as an answer; a job that raises is an answer with no
    ``out``."""
    start = time.perf_counter() - t0
    try:
        out = job(g, part, seed, timing)
    except Exception as e:  # a failed job counts; the window goes on
        print(f"job raised {type(e).__name__}: {e}", file=sys.stderr,
              flush=True)
        out = None
    return {"graph": graph, "out": out, "start": start,
            "end": time.perf_counter() - t0}


def setup(config: dict, mix: dict, seed: int, job, timing: bool) -> dict:
    g, graph = load_graph(config, 0, seed)
    part = config["partition"]
    warm = [answer(g, graph, part, 0, job, timing, time.perf_counter())]
    return {"g": g, "graph": graph, "config": config, "warm": warm}


def window(state: dict, seconds: float, job, timing: bool,
           after_job) -> list:
    import jax

    part = state["config"]["partition"]
    t0 = time.perf_counter()
    out = []
    while time.perf_counter() - t0 < seconds:
        with jax.profiler.TraceAnnotation("job"):  # the host span of a job
            out.append(answer(state["g"], state["graph"], part, 0, job,
                              timing, t0))
        after_job()
    return out
