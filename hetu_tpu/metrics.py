"""Evaluation metrics (reference: python/hetu/metrics.py — accuracy, AUC,
F1, precision/recall, RMSE/MAE/NDCG for rec models).

Implemented on numpy host-side (metrics run on gathered predictions, not in
the jitted step; rank aggregation is the logger's job)."""

from __future__ import annotations

import numpy as np


def accuracy(y_pred, y_true):
    """y_pred: [N, C] logits/probs or [N] class ids; y_true: [N] ids."""
    y_pred = np.asarray(y_pred)
    y_true = np.asarray(y_true).reshape(-1)
    if y_pred.ndim > 1:
        y_pred = np.argmax(y_pred, axis=-1)
    return float(np.mean(y_pred.reshape(-1) == y_true))


def binary_accuracy(scores, y_true, threshold=0.5):
    scores = np.asarray(scores).reshape(-1)
    y_true = np.asarray(y_true).reshape(-1)
    return float(np.mean((scores >= threshold) == (y_true > 0.5)))


def auc(scores, y_true):
    """ROC-AUC via the rank statistic (ties get midranks) — the standard
    CTR metric (reference metrics.py auc)."""
    scores = np.asarray(scores, np.float64).reshape(-1)
    y_true = np.asarray(y_true).reshape(-1) > 0.5
    n_pos = int(y_true.sum())
    n_neg = y_true.size - n_pos
    if n_pos == 0 or n_neg == 0:
        return float("nan")
    order = np.argsort(scores, kind="mergesort")
    ranks = np.empty_like(order, dtype=np.float64)
    sorted_scores = scores[order]
    # midranks for ties
    i = 0
    while i < len(sorted_scores):
        j = i
        while (j + 1 < len(sorted_scores)
               and sorted_scores[j + 1] == sorted_scores[i]):
            j += 1
        ranks[order[i:j + 1]] = 0.5 * (i + j) + 1.0
        i = j + 1
    sum_pos = ranks[y_true].sum()
    return float((sum_pos - n_pos * (n_pos + 1) / 2.0) / (n_pos * n_neg))


def precision_recall_f1(y_pred, y_true, positive=1):
    y_pred = np.asarray(y_pred).reshape(-1)
    y_true = np.asarray(y_true).reshape(-1)
    tp = np.sum((y_pred == positive) & (y_true == positive))
    fp = np.sum((y_pred == positive) & (y_true != positive))
    fn = np.sum((y_pred != positive) & (y_true == positive))
    precision = tp / max(tp + fp, 1)
    recall = tp / max(tp + fn, 1)
    f1 = (2 * precision * recall / max(precision + recall, 1e-12)
          if (precision + recall) > 0 else 0.0)
    return float(precision), float(recall), float(f1)


def f1_score(y_pred, y_true, positive=1):
    return precision_recall_f1(y_pred, y_true, positive)[2]


def rmse(y_pred, y_true):
    y_pred = np.asarray(y_pred, np.float64).reshape(-1)
    y_true = np.asarray(y_true, np.float64).reshape(-1)
    return float(np.sqrt(np.mean((y_pred - y_true) ** 2)))


def mae(y_pred, y_true):
    y_pred = np.asarray(y_pred, np.float64).reshape(-1)
    y_true = np.asarray(y_true, np.float64).reshape(-1)
    return float(np.mean(np.abs(y_pred - y_true)))


def ndcg_at_k(scores, y_true, k=10):
    """NDCG@k for one query (rec-model metric)."""
    scores = np.asarray(scores).reshape(-1)
    y_true = np.asarray(y_true, np.float64).reshape(-1)
    order = np.argsort(-scores)[:k]
    gains = (2.0 ** y_true[order] - 1) / np.log2(np.arange(2, len(order) + 2))
    ideal_order = np.argsort(-y_true)[:k]
    ideal = ((2.0 ** y_true[ideal_order] - 1)
             / np.log2(np.arange(2, len(ideal_order) + 2)))
    denom = ideal.sum()
    return float(gains.sum() / denom) if denom > 0 else 0.0


def confusion_matrix(y_pred, y_true, num_classes):
    y_pred = np.asarray(y_pred).reshape(-1)
    y_true = np.asarray(y_true).reshape(-1)
    m = np.zeros((num_classes, num_classes), np.int64)
    np.add.at(m, (y_true, y_pred), 1)
    return m


# -- serving latency statistics ---------------------------------------------
# The serving engine's percentile math, in exactly one place (linear
# interpolation over the sorted sample, numpy's default — stable for
# small request counts).

def percentile(values, q):
    """q-th percentile (0..100) of a 1-D sample; nan on empty input."""
    values = np.asarray(list(values), np.float64).reshape(-1)
    if values.size == 0:
        return float("nan")
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile q must be in [0, 100], got {q}")
    return float(np.percentile(values, q))


def latency_stats(values, percentiles=(50, 95, 99)):
    """Summary of one latency series: ``{"p50": .., "p95": .., "p99": ..,
    "mean": .., "max": .., "count": n}`` (seconds in, seconds out).
    None entries are dropped (a request that never reached the edge)."""
    values = [v for v in values if v is not None]
    out = {f"p{int(q)}": percentile(values, q) for q in percentiles}
    if values:
        arr = np.asarray(values, np.float64)
        out["mean"] = float(arr.mean())
        out["max"] = float(arr.max())
    else:
        out["mean"] = float("nan")
        out["max"] = float("nan")
    out["count"] = len(values)
    return out


def request_latency_summary(records, keys=("ttft", "tpot", "queue_wait"),
                            percentiles=(50, 95, 99)):
    """Per-key :func:`latency_stats` over serving request records (the
    dicts ``InferenceEngine.records`` accumulates)."""
    return {k: latency_stats((r.get(k) for r in records),
                             percentiles=percentiles) for k in keys}
