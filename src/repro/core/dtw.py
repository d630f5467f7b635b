"""Dynamic Time Warping in JAX.

The paper (§2.1) uses squared-difference DTW:

    DTW(X, Y) = min_P sqrt( sum_k w_k ),   w_k = (x_i - y_j)^2 along path P

with the standard monotone/contiguous warping-path constraints and an
optional Sakoe-Chiba band of radius ``band`` (|i - j| <= band).

Implementation notes
--------------------
The textbook DP has a 2-D dependency (D[i,j] needs D[i-1,j], D[i,j-1],
D[i-1,j-1]).  We scan over *columns* of the DP matrix and resolve the
within-column dependency with the (min,+)-algebra identity:

    D[i] = c_i + min(e_i, D[i-1])            (e_i = min of the two
                                               previous-column entries)
         = C_i + min_{k<=i} (e_k - C_{k-1})  (C = inclusive cumsum of c)
         = C_i + cummin(e - shift(C, 1))

so each column update is a cumsum + cummin — fully parallel on the VPU —
and the whole DTW is a single ``lax.scan`` of length ``m_y``.  This is the
pure-jnp oracle; the TPU hot path is ``repro.kernels.dtw_wavefront``
(anti-diagonal wavefront, candidates on the lane axis).

Band masking is applied *after* the column update (on D, never inside the
cumsum) so the cumulative sums only ever contain real costs — masking with
a BIG constant inside the cumsum would cause catastrophic cancellation for
paths re-entering the band.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

# Large-but-finite "infinity".  float32 max is ~3.4e38; BIG must survive a
# few additions of itself without overflowing.
BIG = jnp.float32(1e30)


def znormalize(x: jnp.ndarray, axis: int = -1, eps: float = 1e-8) -> jnp.ndarray:
    """Z-normalise a time series (UCR-suite convention)."""
    mu = jnp.mean(x, axis=axis, keepdims=True)
    sd = jnp.std(x, axis=axis, keepdims=True)
    return (x - mu) / (sd + eps)


def _column_update(carry_col: jnp.ndarray, cost_col: jnp.ndarray,
                   first_col_mask: jnp.ndarray) -> jnp.ndarray:
    """One DP column via the cumsum/cummin (min,+) identity.

    carry_col: (m_x,) previous column D[:, j-1]  (BIG outside band)
    cost_col:  (m_x,) squared costs c[:, j]      (real values everywhere)
    first_col_mask: scalar bool — True when j == 0 (no left neighbour).
    """
    prev_shift = jnp.concatenate([jnp.full((1,), BIG, carry_col.dtype),
                                  carry_col[:-1]])
    # e_i = min(D[i, j-1], D[i-1, j-1]); for row 0 only the left neighbour.
    e = jnp.minimum(carry_col, prev_shift)
    # For the very first column there is no left neighbour at all:
    # D[i,0] = cumsum(c[:i,0]) — emulate with e_0 = 0, e_i>0 = BIG.
    e0 = jnp.concatenate([jnp.zeros((1,), carry_col.dtype),
                          jnp.full((carry_col.shape[0] - 1,), BIG,
                                   carry_col.dtype)])
    e = jnp.where(first_col_mask, e0, e)
    csum = jnp.cumsum(cost_col)
    shifted = jnp.concatenate([jnp.zeros((1,), csum.dtype), csum[:-1]])
    # D[i] = C_i + cummin_k<=i (e_k - C_{k-1})
    run = jax.lax.associative_scan(jnp.minimum, e - shifted)
    col = csum + run
    return jnp.minimum(col, BIG)


@functools.partial(jax.jit, static_argnames=("band",))
def dtw(x: jnp.ndarray, y: jnp.ndarray,
        band: Optional[int] = None) -> jnp.ndarray:
    """Exact (optionally Sakoe-Chiba banded) squared-DTW cost.

    Args:
      x: (m_x,) float array.
      y: (m_y,) float array.
      band: Sakoe-Chiba radius; ``None`` = unconstrained.  For rectangular
        problems the band is measured around the scaled diagonal.

    Returns:
      scalar: min over warping paths of the summed squared differences.
      (Take ``jnp.sqrt`` for the paper's distance; ranking is identical.)
    """
    x = x.astype(jnp.float32)
    y = y.astype(jnp.float32)
    m_x, m_y = x.shape[0], y.shape[0]
    rows = jnp.arange(m_x)

    if band is None:
        in_band_fn = lambda j: jnp.ones((m_x,), bool)  # noqa: E731
    else:
        slope = m_x / m_y

        def in_band_fn(j):
            center = j * slope
            return jnp.abs(rows - center) <= jnp.maximum(band, 1.0 * abs(m_x - m_y) + band)

    def step(carry, j):
        cost_col = (x - y[j]) ** 2
        col = _column_update(carry, cost_col, j == 0)
        col = jnp.where(in_band_fn(j), col, BIG)
        return col, ()

    init = jnp.full((m_x,), BIG, jnp.float32)
    final_col, _ = jax.lax.scan(step, init, jnp.arange(m_y))
    return final_col[-1]


@functools.partial(jax.jit, static_argnames=("band",))
def dtw_batch(query: jnp.ndarray, candidates: jnp.ndarray,
              band: Optional[int] = None) -> jnp.ndarray:
    """DTW of one query against a batch of candidates: (C, m) -> (C,)."""
    return jax.vmap(lambda c: dtw(query, c, band=band))(candidates)


# ---------------------------------------------------------------------------
# banded window DP (equal lengths) — the CPU analogue of the wavefront
# kernel's O(m·band) cell count, plus threshold-based early abandoning
# ---------------------------------------------------------------------------

def _banded_column(xw, y_j, j, W_prev, r, m):
    """One column of the window DP for a block of candidate lanes:
    W[u, c] = D_c[j - r + u, j], u in [0, 2r+1).

    ``xw`` (2r+1, 1 | C) holds the query values at the window's rows
    (index-clamped), ``y_j`` (C,) the candidates' values at column j.
    Same (min,+) cumsum/cummin identity as ``_column_update``, applied to
    the (2r+1)-wide band window instead of the full column — O(m·band)
    total cells, matching the Pallas wavefront's work bound.  Window
    algebra: D[i, j-1] sits at slot u+1 of the previous column's window,
    D[i-1, j-1] at slot u.  Out-of-matrix slots carry BIG; their (index-
    clamped) costs inside the cumsum cancel exactly because the valid
    slots of a window are contiguous (C_i - C_{k-1} only ever spans valid
    slots for a valid (k, i) pair).  The window runs down the leading
    axis and the candidates along the trailing one, so a TPU keeps the
    (2r+1, C) carry on its lanes instead of padding 2r+1 out to 128.
    """
    u = jnp.arange(2 * r + 1)[:, None]
    i = j - r + u                               # row index of window slot u
    cost = (xw - y_j) ** 2
    up_shift = jnp.concatenate(
        [W_prev[1:], jnp.full((1, W_prev.shape[1]), BIG, W_prev.dtype)])
    e = jnp.minimum(W_prev, up_shift)           # min(D[i-1,j-1], D[i,j-1])
    # j == 0: no left column at all; the path starts at (0, 0) = slot r
    e0 = jnp.where(u == r, 0.0, BIG)
    e = jnp.where(j == 0, e0, e)
    csum = jnp.cumsum(cost, axis=0)
    shifted = jnp.concatenate([jnp.zeros_like(csum[:1]), csum[:-1]])
    run = jax.lax.associative_scan(jnp.minimum, e - shifted, axis=0)
    col = jnp.minimum(csum + run, BIG)
    return jnp.where((i >= 0) & (i < m), col, BIG)


def _dtw_banded_lanes(xs: jnp.ndarray, ys: jnp.ndarray, band: int,
                      threshold: Optional[jnp.ndarray]) -> jnp.ndarray:
    """Window-DP DTW over time-major arrays: ``xs`` (m, 1 | C) query
    (one shared or one per lane), ``ys`` (m, C) candidates -> (C,).

    A lane stops at the first column whose window minimum exceeds its
    threshold (its window is then frozen); the loop ends when every lane
    has stopped or the last column is done.
    """
    xs = xs.astype(jnp.float32)
    ys = ys.astype(jnp.float32)
    m, c = ys.shape
    assert xs.shape[0] == m, "dtw_banded requires equal lengths"
    r = min(band, m - 1)
    win = jnp.arange(2 * r + 1)
    thr = jnp.float32(BIG) if threshold is None \
        else jnp.asarray(threshold, jnp.float32)

    def cond(carry):
        j, _, alive = carry
        return (j < m) & jnp.any(alive)

    def body(carry):
        j, W, alive = carry
        xw = xs[jnp.clip(j - r + win, 0, m - 1)]
        W = jnp.where(alive, _banded_column(xw, ys[j], j, W, r, m), W)
        return j + 1, W, alive & (jnp.min(W, axis=0) <= thr)

    _, W, _ = jax.lax.while_loop(
        cond, body, (0, jnp.full((2 * r + 1, c), BIG, jnp.float32),
                     jnp.ones((c,), bool)))
    out = W[r]                                  # D[m-1, m-1]
    if threshold is None:
        return out
    return jnp.where(out > thr, BIG, out)


@functools.partial(jax.jit, static_argnames=("band",))
def dtw_banded(x: jnp.ndarray, y: jnp.ndarray, band: int,
               threshold: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Equal-length banded squared-DTW via a (2r+1)-wide window DP.

    Value-equivalent to ``dtw(x, y, band=band)`` (up to float summation
    order) at O(m·band) instead of O(m²) work.  ``threshold`` enables
    early abandoning: the column minimum is a sound lower bound on the
    final cost (every monotone warping path visits every column), so the
    scan stops once it exceeds ``threshold`` and the contract becomes
    *exact value if DTW <= threshold, else BIG* — same as the
    threshold-aware Pallas wavefront.  ``None`` runs all columns and
    returns the exact value.
    """
    return _dtw_banded_lanes(x[:, None], y[:, None], band, threshold)[0]


@functools.partial(jax.jit, static_argnames=("band",))
def dtw_banded_batch(query: jnp.ndarray, candidates: jnp.ndarray, band: int,
                     threshold: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Banded window-DP DTW of one query vs a batch: (C, m) -> (C,).

    ``threshold`` broadcasts over lanes (scalar or (C,)); the column scan
    stops once every lane is done or abandoned, so a block of hopeless
    candidates exits after a prefix of the columns.
    """
    return _dtw_banded_lanes(query[:, None], candidates.T, band, threshold)


@functools.partial(jax.jit, static_argnames=("band",))
def dtw_banded_pairs(queries: jnp.ndarray, candidates: jnp.ndarray, band: int,
                     threshold: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Row-aligned banded window-DP DTW: (P, m) x (P, m) -> (P,)."""
    return _dtw_banded_lanes(queries.T, candidates.T, band, threshold)


@functools.partial(jax.jit, static_argnames=("band",))
def dtw_pairwise(xs: jnp.ndarray, ys: jnp.ndarray,
                 band: Optional[int] = None) -> jnp.ndarray:
    """All-pairs DTW: xs (A, m), ys (B, m) -> (A, B)."""
    return jax.vmap(lambda q: dtw_batch(q, ys, band=band))(xs)


def dtw_distance(x: jnp.ndarray, y: jnp.ndarray,
                 band: Optional[int] = None) -> jnp.ndarray:
    """Paper-convention distance: sqrt of the summed squared path cost."""
    return jnp.sqrt(dtw(x, y, band=band))


def dtw_dp_reference(x, y, band=None):
    """O(m^2) numpy-style DP, for tests only (the 'obviously correct' DTW)."""
    import numpy as np
    x = np.asarray(x, np.float64)
    y = np.asarray(y, np.float64)
    m_x, m_y = len(x), len(y)
    D = np.full((m_x, m_y), np.inf)
    slope = m_x / m_y
    for j in range(m_y):
        for i in range(m_x):
            if band is not None:
                width = max(band, abs(m_x - m_y) + band)
                if abs(i - j * slope) > width:
                    continue
            c = (x[i] - y[j]) ** 2
            if i == 0 and j == 0:
                D[i, j] = c
            else:
                best = np.inf
                if i > 0:
                    best = min(best, D[i - 1, j])
                if j > 0:
                    best = min(best, D[i, j - 1])
                if i > 0 and j > 0:
                    best = min(best, D[i - 1, j - 1])
                D[i, j] = c + best
    return D[-1, -1]
