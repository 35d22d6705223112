"""The numpy check of an answer, on a hand-built graph."""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from bench import check  # noqa: E402

# a 4-cycle 0-1-2-3-0 with the edge {0, 1} drawn twice, and vertex 4 alone
GRAPH = (5, np.array([0, 0, 1, 2]), np.array([1, 3, 2, 3]),
         np.array([2, 1, 1, 1]))
LIMITS = dict.fromkeys(check.NUMBERS, 0)


def test_sound_answer_reads_zero():
    labels = np.array([0, 0, 1, 1, 1])
    # cut {1,2} and {0,3}; blocks weigh 2 and 3; L_max = 1.5 * 3 = 4.5
    nums = check.check_answer(GRAPH, 2, 0.5, labels, 2.0, 3 / 2.5 - 1)
    assert nums == {"labels_bad": 0, "cut_gap": 0.0, "imbalance_gap": 0.0,
                    "balance_excess": 3 - 4.5, "cut_ref": 2}
    assert check.verdict(nums, LIMITS)


def test_each_fault_reads_above_its_limit():
    labels = np.array([0, 1, 1, 0, 0])
    nums = check.check_answer(GRAPH, 2, 0.0, labels, 2.0, 0.2)
    assert nums["cut_gap"] == 1.0            # cut is 2 + 1 = 3
    assert nums["balance_excess"] == 0.0     # 3 vertices, L_max = 3
    assert check.verdict(
        check.check_answer(GRAPH, 2, 0.0, labels, 3.0, 3 / 2.5 - 1), LIMITS)
    over = check.check_answer(GRAPH, 2, 0.0, np.zeros(5, int), 0.0, 1.0)
    assert over["balance_excess"] == 2.0 and not check.verdict(over, LIMITS)
    short = check.check_answer(GRAPH, 2, 0.5, labels[:3], 3.0, 0.2)
    assert short["labels_bad"] == 2 and not check.verdict(short, LIMITS)
    wild = check.check_answer(GRAPH, 2, 0.5, np.array([0, 1, 1, 0, 7]),
                              3.0, 0.2)
    assert wild["labels_bad"] == 1
    wrong_imb = check.check_answer(GRAPH, 2, 0.5, labels, 3.0, 0.0)
    assert wrong_imb["imbalance_gap"] == 3 / 2.5 - 1 and not check.verdict(
        wrong_imb, LIMITS)


def test_worst_takes_each_number_apart():
    a = dict(labels_bad=0, cut_gap=3.0, imbalance_gap=0.0, balance_excess=-1)
    b = dict(labels_bad=1, cut_gap=0.0, imbalance_gap=0.5, balance_excess=-4)
    assert check.worst([a, b]) == dict(labels_bad=1, cut_gap=3.0,
                                       imbalance_gap=0.5, balance_excess=-1)
