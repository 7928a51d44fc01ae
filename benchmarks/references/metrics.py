"""Plain validation metrics for the references."""

import numpy as np


def auc(scores, labels) -> float:
    """Area under the ROC curve by ranks, ties sharing their mean rank."""
    scores = np.asarray(scores, np.float64)
    pos = np.asarray(labels) > 0.5
    order = np.argsort(scores, kind="stable")
    sorted_scores = scores[order]
    ranks = np.empty(len(scores), np.float64)
    ranks[order] = np.arange(1, len(scores) + 1)
    # Mean rank within each run of equal scores.
    starts = np.flatnonzero(np.r_[True, sorted_scores[1:] != sorted_scores[:-1]])
    ends = np.r_[starts[1:], len(scores)]
    mean_rank = (starts + 1 + ends) / 2.0
    ranks[order] = np.repeat(mean_rank, ends - starts)
    n_pos, n_neg = int(pos.sum()), int((~pos).sum())
    return float((ranks[pos].sum() - n_pos * (n_pos + 1) / 2.0) / max(n_pos * n_neg, 1))
