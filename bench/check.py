"""The comparison that decides ``correct``: a partition checked against the
graph, in numpy, from the benchmark's own edge list.

A job's answer is its labels and the cut and imbalance it reports.  The
plain semantics (KaMinPar's convention, the repository's DESIGN §1):

    block weight  c(V_i) = number of vertices labelled i (unit weights)
    L_max         = (1 + eps) * ceil(n / k)
    cut           = sum of the multiplicities of pairs {a, b} with
                    label[a] != label[b]
    imbalance     = max_i c(V_i) / (n / k) - 1

Each number below is 0 (or at most 0) for a sound answer; the limits are in
the configuration file, beside the guarantees they hold the program to.
"""

from __future__ import annotations

import numpy as np

NUMBERS = ("labels_bad", "cut_gap", "imbalance_gap", "balance_excess")


def reference_cut(a: np.ndarray, b: np.ndarray, w: np.ndarray,
                  labels: np.ndarray) -> int:
    return int(w[labels[a] != labels[b]].sum())


def reference_imbalance(block_w: np.ndarray, n: int, k: int) -> float:
    return float(block_w.max() / (n / k) - 1.0)


def check_answer(graph, k: int, eps: float, labels, cut, imbalance) -> dict:
    """The numbers compared for one answer.

    ``graph`` is ``(n, a, b, w)`` from :func:`graphs.edge_list`.
    labels_bad      vertices with no label or one outside [0, k), plus
                    labels beyond the n vertices
    cut_gap         |reported cut - recomputed cut|
    imbalance_gap   |reported imbalance - recomputed imbalance|
    balance_excess  heaviest block minus L_max (at most 0 when balanced)

    ``cut_ref``, the recomputed cut, comes with them; it is reported, not
    compared.
    """
    n, a, b, w = graph
    lab = np.asarray(labels).reshape(-1)
    bad = abs(int(lab.shape[0]) - n)
    lab = lab[:n].astype(np.int64)
    out_of_range = (lab < 0) | (lab >= k)
    bad += int(out_of_range.sum())
    if lab.shape[0] < n:
        lab = np.concatenate([lab, np.full(n - lab.shape[0], -1, np.int64)])
    # a vertex with no valid label weighs in no block and is cut from
    # every labelled neighbour
    safe = np.where((lab < 0) | (lab >= k), -1, lab)
    block_w = np.bincount(safe[safe >= 0], minlength=k)[:k]
    lmax = (1.0 + eps) * np.ceil(n / k)
    cut_ref = reference_cut(a, b, w, safe)
    return {
        "labels_bad": bad,
        "cut_gap": abs(float(cut) - cut_ref),
        "imbalance_gap": abs(float(imbalance)
                             - reference_imbalance(block_w, n, k)),
        "balance_excess": float(block_w.max() - lmax),
        "cut_ref": cut_ref,
    }


def worst(readings: list[dict]) -> dict:
    """Largest reading of each number over a run's answers."""
    return {name: max(r[name] for r in readings) for name in NUMBERS}


def verdict(numbers: dict, limits: dict) -> bool:
    return all(numbers[name] <= limits[name] for name in NUMBERS)
