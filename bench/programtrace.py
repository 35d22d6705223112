"""The program's own spans and device scopes in a profiler trace.

The partitioner opens host spans (``repro.telemetry``: the call, the
graph's sharding, its phases, each coarsening and refinement level, each
cluster round, contraction and projection, every device-to-host scalar
read as ``host_read/<site>``) and names device scopes inside its fused level
programs (``repro.refine.engine``: ``gain``, ``move``, ``afterburner``,
``rebalance``, ``cut``).

A v5e trace's op events carry neither the scope nor the op metadata: an
event's name is its HLO text without ``metadata``, and its stats are
timing alone.  The scope of each op is the ``op_name`` of the compiled
program's metadata, which xprof's ``hlo_stats`` gives per HLO op with its
self time (:func:`hlo_op_rows`).  Everything else here is arithmetic on
``(name, start_ns, end_ns)`` tuples, which the tests drive with synthetic
ones:

* :func:`scope_self_s` — seconds per rung scope, each op counted toward
  its innermost scope only (an op under ``move/gain`` is ``gain`` time);
* :func:`labeller` and :func:`idle_gaps` — each idle gap named by the path
  of program spans open at its middle (``job/dpartition/coarsen/
  coarsen/level/host_read/nc``), or, where no program span is open, by the
  label ``tracereduce`` gives it;
* :func:`paired_offsets` — host spans against the device programs they
  wait for, on the trace's two clocks.

A program without these spans or scopes gives empty results, not errors.
"""

from __future__ import annotations

import glob
import json
import os
from collections import defaultdict

from bench import tracereduce

SCOPES = ("gain", "move", "afterburner", "rebalance", "cut")
# names of the program's host spans (``repro.telemetry``), and the prefix of
# the host reads' spans
SPAN_NAMES = ("dpartition", "shard", "coarsen", "init", "refine",
              "coarsen/level", "cluster_round", "contract", "uncoarsen",
              "init/restart", "refine/level")
READ_PREFIX = "host_read/"
MODULES_LINE = "XLA Modules"
# the fused refinement level programs (``repro.refine.drivers``) of the
# V-cycle's refine phase, by the name of the function they compile
LEVEL_PROGRAMS = ("refine_allgather", "refine_halo")


def is_program_span(name: str) -> bool:
    return name in SPAN_NAMES or name.startswith(READ_PREFIX)


def xplane_path(profiler_dir) -> str:
    """The newest ``.xplane.pb`` under the dir."""
    paths = sorted(glob.glob(os.path.join(str(profiler_dir), "**",
                                          "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {profiler_dir}")
    return paths[-1]


def load(path: str) -> tuple[list, list]:
    """``(spans, programs)`` of one trace: the host spans named ``job`` or
    a program span, and the device programs (the ``XLA Modules`` line of
    the first device), each ``(name, start_ns, end_ns)`` in start order."""
    from jax.profiler import ProfileData

    spans, programs = [], {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(tracereduce.DEVICE_PLANE):
            programs[plane.name] = [
                (e.name, e.start_ns, e.start_ns + e.duration_ns)
                for line in plane.lines if line.name == MODULES_LINE
                for e in line.events]
        elif plane.name == tracereduce.HOST_PLANE:
            spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                      for line in plane.lines for e in line.events
                      if e.name == "job" or is_program_span(e.name)]
    first = min(programs, default=None,
                key=lambda d: int(d[len(tracereduce.DEVICE_PLANE):]))
    return (sorted(spans, key=lambda s: s[1]),
            sorted(programs.get(first, []), key=lambda p: p[1]))


def hlo_op_rows(path: str) -> list[dict]:
    """Per HLO op of the trace, from xprof's ``hlo_stats``: ``program_id``,
    ``op_name`` (the metadata's name path, which holds the scopes) and
    ``self_s`` (device seconds outside the ops it holds)."""
    from xprof.convert import raw_to_tool_data

    data, _ = raw_to_tool_data.xspace_to_tool_data(
        [path], "hlo_stats", {"use_saved_result": False})
    table = json.loads(data)
    ids = [c["id"] for c in table["cols"]]
    rows = [dict(zip(ids, (c["v"] if c else None for c in r["c"])))
            for r in table["rows"]]
    return [{"program_id": r["program_id"], "op_name": r["tf_op_name"] or "",
             "self_s": r["total_self_time"] * 1e-6} for r in rows]


def program_id(name: str) -> str:
    """``"123"`` of a device program named ``jit_refine_allgather(123)``."""
    return name[name.rfind("(") + 1:-1] if name.endswith(")") else name


def innermost_scope(path: str, scopes=SCOPES) -> str | None:
    """The last component of a ``/``-separated name path that is a scope."""
    for part in reversed(path.split("/")):
        if part in scopes:
            return part
    return None


def scope_self_s(rows) -> dict:
    """``{scope: seconds}`` over ``(op_name, self_seconds)`` rows, each op
    counted toward its innermost scope only, ``None`` for ops in no
    scope."""
    acc: dict = defaultdict(float)
    for name, seconds in rows:
        acc[innermost_scope(name)] += seconds
    return dict(acc)


def span_paths(spans) -> list:
    """``(path, name, start, end)`` of each span, its path the names of the
    spans that hold it and its own, joined by ``/``.  A span holds another
    that starts and ends inside it."""
    out, stack = [], []
    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and not (stack[-1][1] <= s and e <= stack[-1][2]):
            stack.pop()
        path = f"{stack[-1][0]}/{name}" if stack else name
        stack.append((path, s, e))
        out.append((path, name, s, e))
    return out


def labeller(spans, timings):
    """``label(t)``: the path of the innermost program span open at ``t``
    (``spans`` holds the benchmark's ``job`` spans and the program's);
    where none is open, the label ``tracereduce`` gives from the jobs and
    the phase lengths of ``timings``."""
    paths = [(path, s, e) for path, name, s, e in span_paths(spans)
             if is_program_span(name)]
    fallback = tracereduce.phase_spans([x for x in spans if x[0] == "job"],
                                       timings)

    def label(t: float) -> str:
        inner = None
        for path, s, e in paths:
            if s <= t < e and (inner is None or s >= inner[1]):
                inner = (path, s)
        return inner[0] if inner else tracereduce.label_at(t, fallback)

    return label


def idle_gaps(intervals, t0: float, t1: float, label,
              top: int = tracereduce.TOP) -> list[list]:
    """The longest gaps between device intervals inside ``[t0, t1)``, each
    ``[label(middle), seconds]``."""
    gaps, cur = [], t0
    for s, e in intervals:
        if s > cur:
            gaps.append((cur, min(s, t1)))
        cur = max(cur, e)
        if cur >= t1:
            break
    if cur < t1:
        gaps.append((cur, t1))
    gaps = sorted(((s, e) for s, e in gaps if e > s),
                  key=lambda g: -(g[1] - g[0]))
    return [[label((s + e) / 2), (e - s) * 1e-9] for s, e in gaps[:top]]


def paired_offsets(spans, programs) -> list[tuple[float, float]]:
    """``(program start - span start, span end - program end)`` in seconds
    for the i-th span and the i-th program, both in start order: for
    dispatch spans against the programs they launch, the first is the
    launch latency; for host-read spans against the programs whose result
    they read, the second is the read's latency.  On one clock neither
    is negative."""
    return [((p[1] - s[1]) * 1e-9, (s[2] - p[2]) * 1e-9)
            for s, p in zip(sorted(spans, key=lambda x: x[1]),
                            sorted(programs, key=lambda x: x[1]))]
