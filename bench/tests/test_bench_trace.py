"""The trace reduction on synthetic events: the busy union with
overlapping events, the idle share, kernel time by name, the top ops and
the idle gaps labelled by the host span open at the time."""

import importlib.util
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import pytest  # noqa: E402

from bench import harness, kernelbytes, tracereduce  # noqa: E402

# (name, start_ns, end_ns, hlo text); gather.1 overlaps fusion.2
EVENTS = [
    ("fusion.2", 0, 100, ""),
    ("gather.1", 50, 150, ""),
    ("gather.1", 400, 500, ""),
    ("gain_scoreboard_pallas.3", 700, 800, ""),
]


def test_busy_union_merges_overlaps():
    assert tracereduce.busy_intervals(EVENTS) == [(0, 150), (400, 500),
                                                  (700, 800)]
    assert tracereduce.busy_ns(EVENTS) == 350


def test_loops_are_busy_but_not_top_ops():
    loop = ("%while.3 while (s32[])", 0, 1000,
            "%while.3 = (s32[]{:T(128)}, s32[8]{0}) while((s32[], s32[8]) "
            "%tuple.1), condition=%c, body=%b")
    events = EVENTS + [loop]
    assert tracereduce.busy_ns(events) == 1000
    assert "%while.3 while (s32[])" not in [
        name for name, _ in tracereduce.top_ops(events)]
    assert tracereduce.op_label(loop[3]) == (
        "%while.3 while (s32[]{:T(128)}, s32[8]{0})")
    assert tracereduce.op_label(OPTIMIZED).startswith(
        "%gain_scoreboard_pallas.15 custom-call (f32[1,32768]")


def test_top_ops_and_kernel_time():
    top = tracereduce.top_ops(EVENTS)
    assert top[0] == ["gather.1", pytest.approx(200e-9)]
    assert [name for name, _ in top] == ["gather.1", "fusion.2",
                                         "gain_scoreboard_pallas.3"]
    hit = tracereduce.kernel_events(EVENTS, kernelbytes.GAIN_KERNEL)
    assert sum(e[2] - e[1] for e in hit) == 100


def test_idle_gaps_are_labelled_by_the_open_span():
    spans = tracereduce.phase_spans(
        [("job", 0, 1000)],
        [{"coarsen_s": 300e-9, "init_s": 100e-9, "refine_s": 500e-9}])
    assert spans[1:] == [("job/coarsen", 0, 300e-9 * 1e9),
                         ("job/init", 300e-9 * 1e9, 400e-9 * 1e9),
                         ("job/refine", 400e-9 * 1e9, 900e-9 * 1e9)]
    gaps = tracereduce.idle_gaps(tracereduce.busy_intervals(EVENTS), 0,
                                 1000, spans)
    # gaps [150, 400), [500, 700) and [800, 1000), named at their middles
    assert [g[0] for g in gaps] == ["job/coarsen", "job/refine", "job"]
    assert [round(g[1] * 1e9) for g in gaps] == [250, 200, 200]


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name, ROOT / "bench" / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_idle_share_and_roofline_readers():
    run = {"trace": {"busy_s": 3.0, "window_s": 4.0, "events": EVENTS},
           "device_kind": "TPU v5 lite"}
    assert _reader("device_idle")(run) == pytest.approx(25.0)
    # no shapes in the HLO text: the roofline reader reports nothing
    assert _reader("gain_kernel_roofline")(run) is None
    assert _reader("gain_kernel_roofline")({"trace": None}) is None
    assert harness.load_reader("device_idle")(run) == pytest.approx(25.0)


# the kernel's call as a v5e trace names it
OPTIMIZED = (
    "%gain_scoreboard_pallas.15 = (f32[1,32768]{1,0:T(1,128)}, "
    "f32[1,32768]{1,0:T(1,128)S(1)}, s32[1,32768]{1,0:T(1,128)S(1)}) "
    "custom-call(%reshape.546, %copy-done.48, %bitcast.16, %bitcast.17, "
    "%pad.38), custom_call_target=\"tpu_custom_call\", "
    "operand_layout_constraints={s32[32,32768]{1,0}, f32[32,32768]{1,0}, "
    "s32[1,32768]{1,0}, f32[1,32768]{1,0}, f32[128,1]{1,0}}, "
    "frontend_attributes={kernel_metadata={}}")

def test_gain_kernel_shapes_and_bytes():
    assert kernelbytes.gain_call_shapes(OPTIMIZED) == (32, 32768, 128)
    assert kernelbytes.gain_call_shapes("%fusion.3 = f32[8] fusion()") is None
    assert kernelbytes.gain_kernel_bytes(32, 1024, 128) == 4 * (
        2 * 32 * 1024 + 5 * 1024 + 128)


def test_roofline_share_from_shapes_and_time():
    nbytes = kernelbytes.gain_kernel_bytes(32, 32768, 128)
    ns = 2 * nbytes / 819e9 * 1e9  # each call takes twice its bound
    events = [("gain_scoreboard_pallas.15", 0, ns, OPTIMIZED),
              ("gain_scoreboard_pallas.15", ns, 2 * ns, OPTIMIZED),
              ("fusion.1", 0, 10 * ns, "")]
    run = {"trace": {"events": events}, "device_kind": "TPU v5 lite"}
    assert _reader("gain_kernel_roofline")(run) == pytest.approx(50.0)
