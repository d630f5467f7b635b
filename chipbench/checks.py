"""How ``correct`` is decided: the served top-k against the plain reference.

After the window has closed, a sample of the answered requests, drawn
from the seed, is searched again by ``chipbench.reference`` over the same
archive.  SSH re-ranks the ``top_c`` rows with the most hash collisions;
which rows of a group tied at that cut are taken is not part of it, so
the reference resolves the tie as the served list does
(``Reference.search_as_served``).  Four numbers are compared, each with
its limit (the configuration's ``check.limits``, and 0 for the counts):

* ``rank_gap`` — the largest relative gap, rank by rank, between a served
  distance and the reference's distance at that rank (a missed neighbour,
  a wrong order or a short list shows here; a missing rank reads 1e30);
* ``pair_gap`` — the largest relative gap between a served distance and
  the reference's DTW of the series the served id names (a wrong id or a
  wrong distance shows here);
* ``foreign`` — served ids that no resolution of the tie makes a
  candidate: rows under the cut, or more tied rows than the cut leaves
  places for;
* ``unanswered`` — requests of the window that never got an answer.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

MISSING = 1e30      # gap reported for a rank the served list lacks
_TINY = 1e-12


def rel_gap(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return np.abs(a - b) / np.maximum(np.abs(b), _TINY)


def compare(served_ids: Sequence[np.ndarray],
            served_d: Sequence[np.ndarray], ref_d: np.ndarray,
            pair_ref_d: Sequence[np.ndarray]) -> Dict[str, float]:
    """Gaps of served lists against the reference's (S, k) distances and
    the reference DTW of each served id (``pair_ref_d``)."""
    rank, pair = 0.0, 0.0
    for ids, d, want, pd in zip(served_ids, served_d, ref_d, pair_ref_d):
        k = int(np.sum(np.isfinite(want)))
        got = np.asarray(d, np.float64)[:k]
        if got.shape[0] < k:
            rank = max(rank, MISSING)
        if got.shape[0]:
            rank = max(rank, float(rel_gap(got, want[:got.shape[0]]).max()))
            pair = max(pair, float(rel_gap(got, pd[:got.shape[0]]).max()))
        if len(set(int(i) for i in ids)) != len(ids):
            rank = max(rank, MISSING)
    return {"rank_gap": min(rank, MISSING), "pair_gap": min(pair, MISSING)}


def sample(answered: List[int], n: int, seed: int) -> List[int]:
    """``n`` answered requests drawn from ``seed`` (all, if fewer)."""
    rng = np.random.default_rng([int(seed), 2])
    if len(answered) <= n:
        return list(answered)
    return sorted(int(k) for k in rng.choice(answered, n, replace=False))


def reference_readings(ref, archive, db_sigs, queries: np.ndarray,
                       served_ids, served_d) -> Dict[str, float]:
    """Compare served answers for ``queries`` with ``ref``'s search."""
    _, want, foreign = ref.search_as_served(archive, db_sigs, queries,
                                            served_ids)
    k = max(len(w) for w in want)
    want = np.stack([np.pad(np.asarray(w, np.float64), (0, k - len(w)),
                            constant_values=np.inf) for w in want])
    pair_ref = ref._pair_dtw(archive, queries,
                             [np.asarray(ids, np.int64) for ids in served_ids])
    out = compare(served_ids, served_d, want, pair_ref)
    out["foreign"] = int(np.sum(foreign))
    return out


def verdict(readings: Dict[str, float], unanswered: int,
            limits: Dict[str, float]) -> Dict[str, Dict[str, float]]:
    """Each compared number beside its limit."""
    out = {name: {"value": float(readings[name]),
                  "limit": float(limits[name])}
           for name in ("rank_gap", "pair_gap")}
    out["foreign"] = {"value": int(readings["foreign"]), "limit": 0}
    out["unanswered"] = {"value": int(unanswered), "limit": 0}
    return out


def passed(checks: Dict[str, Dict[str, float]]) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
