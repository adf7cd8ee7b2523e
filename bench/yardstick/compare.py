"""The comparison that decides ``correct``: answers against the plain
reference, each number beside its limit."""

from __future__ import annotations

import numpy as np

# scores within this relative gap are a tie (``chip_smoke.py`` ``near_tie``)
TIE_REL = 1e-6


def compare_topk(got_i, got_s, ref_i, ref_s, true_got, topk,
                 tie_rel=TIE_REL):
    """Compare returned top-k lists with the reference.

    got_i/got_s: [m, topk] returned indices and scores; ref_i/ref_s:
    [m, topk + 1] reference indices and float64 scores; true_got: [m,
    topk] float64 score of each returned index (NaN if it is no row).

    A row is right when its indices equal the reference's, or, where they
    differ, when its indices are distinct rows whose true scores equal the
    reference's score at the same rank within ``tie_rel`` (a swap among
    equal scores).  Returns ``wrong_rows`` (rows that are neither) and
    ``score_rel_err``: the largest gap between a returned score and the
    true score of the returned index, over the row's largest reference
    score magnitude.
    """
    got_i = np.asarray(got_i, np.int64)
    got_s = np.asarray(got_s, np.float64)
    true_got = np.asarray(true_got, np.float64)
    want_s = np.asarray(ref_s, np.float64)[:, :topk]
    same = (got_i == np.asarray(ref_i)[:, :topk]).all(1)
    srt = np.sort(got_i, 1)
    distinct = (srt[:, 1:] != srt[:, :-1]).all(1)
    tol = tie_rel * np.maximum(np.abs(true_got), np.abs(want_s))
    swap = (distinct & np.isfinite(true_got).all(1)
            & (np.abs(true_got - want_s) <= tol).all(1))
    wrong = ~same & ~swap
    scale = np.maximum(np.abs(want_s).max(1, initial=0.0), 1e-30)
    gap = np.abs(got_s - true_got) / scale[:, None]
    gap = np.where(np.isfinite(true_got), gap, np.inf)
    err = float(gap.max(initial=0.0)) if len(gap) else 0.0
    return {"wrong_rows": int(wrong.sum()), "score_rel_err": err}


def invalid_rows(idx, scores, n_rows, self_rows=None):
    """Rows of a returned answer that cannot be right whatever the data:
    an index outside [0, n_rows), a repeated index, the row itself (for a
    k-NN graph), or scores that are not finite and non-increasing."""
    idx = np.asarray(idx)
    scores = np.asarray(scores)
    bad = ((idx < 0) | (idx >= n_rows)).any(1)
    srt = np.sort(idx, 1)
    bad |= (srt[:, 1:] == srt[:, :-1]).any(1)
    if self_rows is not None:
        bad |= (idx == np.asarray(self_rows)[:, None]).any(1)
    bad |= ~np.isfinite(scores).all(1)
    bad |= (scores[:, 1:] > scores[:, :-1]).any(1)
    return int(bad.sum())


def checks_line(checks):
    """``{name: {"value": v, "limit": l}}`` of [(name, value, limit)] and
    whether every value is within its limit."""
    out = {name: {"value": value, "limit": limit}
           for name, value, limit in checks}
    ok = all(value <= limit for _, value, limit in checks)
    return out, ok
