"""Reduction of a profiler trace to the numbers the benchmark reports.

The loader reads the ``.xplane.pb`` that ``jax.profiler`` writes, through
``jax.profiler.ProfileData``: device ops from the ``XLA Ops`` line of every
``/device:TPU:<i>`` plane, and the benchmark's own host spans
(``TraceAnnotation``) from the host plane, on the same clock.  Everything
after loading is plain arithmetic on ``(name, start_ns, end_ns)`` tuples,
which the tests drive with synthetic events.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
TOP = 10


def load(profiler_dir: str, span_names) -> tuple[dict, list]:
    """``({device: [(op, start_ns, end_ns, hlo_text)]},
    [(span, start_ns, end_ns)])`` from the newest trace under the dir.  On a
    TPU an op's event name is its HLO instruction text; ``op`` is its
    :func:`op_label`."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(str(profiler_dir), "**",
                                          "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {profiler_dir}")
    data = ProfileData.from_file(paths[-1])
    devices, spans = {}, []
    wanted = set(span_names)
    labels: dict[str, str] = {}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    text = e.name
                    if text not in labels:
                        labels[text] = op_label(text)
                    ops.append((labels[text], e.start_ns,
                                e.start_ns + e.duration_ns, text))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name in wanted:
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
    return devices, sorted(spans, key=lambda s: s[1])


_OPCODE = re.compile(r"[)}\]] ([a-z][\w-]*)\(")
# ops that only hold others: their time is their body's
CONTAINERS = ("while", "conditional", "call")


def opcode(text: str) -> str:
    m = _OPCODE.search(text)
    return m.group(1) if m else ""


def op_label(text: str) -> str:
    """``%name opcode result-shape`` of an HLO instruction text, cut to 120
    characters; other names pass unchanged."""
    if " = " not in text:
        return text
    name, rest = text.split(" = ", 1)
    code = opcode(text)
    shape = rest.split(f" {code}(", 1)[0] if code else ""
    return f"{name} {code} {shape}"[:120]


def busy_intervals(events) -> list[tuple[float, float]]:
    """The union of the events' ``[start, end)`` intervals, merged."""
    merged: list[list[float]] = []
    for start, end in sorted((e[1], e[2]) for e in events):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(s, e) for s, e in merged]


def busy_ns(events) -> float:
    return sum(e - s for s, e in busy_intervals(events))


def top_ops(events, top: int = TOP) -> list[list]:
    """``[[op, seconds]]`` of the ops that took most device time, loops and
    other ops that only hold others left out."""
    acc: dict[str, float] = defaultdict(float)
    held: dict[str, bool] = {}
    for e in events:
        if e[3] not in held:
            held[e[3]] = opcode(e[3]) in CONTAINERS
        if not held[e[3]]:
            acc[e[0]] += e[2] - e[1]
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:top]
    return [[name, ns * 1e-9] for name, ns in ranked]


def kernel_events(events, name_part: str) -> list:
    """Events whose op name or HLO text names the kernel."""
    return [e for e in events if name_part in e[0] or name_part in e[3]]


def label_at(t: float, spans) -> str:
    """Name of the innermost (latest-starting) span open at ``t``."""
    best = None
    for name, s, e in spans:
        if s <= t < e and (best is None or s >= best[1]):
            best = (name, s)
    return best[0] if best else "outside spans"


def idle_gaps(intervals, t0: float, t1: float, spans,
              top: int = TOP) -> list[list]:
    """The longest gaps between device intervals inside ``[t0, t1)``, each
    ``[label of the host span open at its middle, seconds]``."""
    gaps, cur = [], t0
    for s, e in intervals:
        if s > cur:
            gaps.append((cur, min(s, t1)))
        cur = max(cur, e)
        if cur >= t1:
            break
    if cur < t1:
        gaps.append((cur, t1))
    gaps = [(s, e) for s, e in gaps if e > s]
    gaps.sort(key=lambda g: -(g[1] - g[0]))
    return [[label_at((s + e) / 2, spans), (e - s) * 1e-9]
            for s, e in gaps[:top]]


def phase_spans(job_spans, timings) -> list:
    """Each job span, and inside it the program's phases laid end to end
    from the job's start with the lengths ``dpartition(timing=True)``
    measured (``coarsen_s``, ``init_s``, ``refine_s``).  The phases start
    after the graph is sharded, a few milliseconds in, so their edges are
    that much early."""
    out = []
    for (name, s, e), tm in zip(job_spans, timings):
        out.append((name, s, e))
        cur = s
        for phase in ("coarsen_s", "init_s", "refine_s"):
            length = (tm or {}).get(phase, 0.0) * 1e9
            out.append((f"{name}/{phase[:-2]}", cur, min(cur + length, e)))
            cur += length
    return out


def summarize(profiler_dir, timings, window_s: float) -> dict:
    """What the harness and the metric readers take from one trace."""
    devices, spans = load(profiler_dir, ("job",))
    if not devices:
        raise RuntimeError(f"the trace under {profiler_dir} has no device "
                           f"plane")
    all_events = [e for evs in devices.values() for e in evs]
    busy = [busy_ns(evs) * 1e-9 for evs in devices.values()]
    jobs = [s for s in spans if s[0] == "job"]
    labelled = phase_spans(jobs, timings)
    first = min(devices, key=lambda d: int(d[len(DEVICE_PLANE):]))
    intervals = busy_intervals(devices[first])
    t0 = jobs[0][1] if jobs else (intervals[0][0] if intervals else 0.0)
    t1 = jobs[-1][2] if jobs else (intervals[-1][1] if intervals else 0.0)
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": window_s,
        "top_ops": top_ops(all_events),
        "idle_gaps": idle_gaps(intervals, t0, t1, labelled),
        "events": all_events,
    }
