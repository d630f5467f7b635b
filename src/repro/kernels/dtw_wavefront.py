"""Pallas TPU kernel — banded DTW re-rank, anti-diagonal wavefront.

The DTW re-rank (paper Alg. 2 line 10) is the compute hot-spot of an SSH
query: O(C · m · band) after hashing prunes N → C.  GPU implementations
assign one thread per DP cell along the wavefront; the TPU adaptation maps

  * candidates → the 128-wide lane axis (one DTW per lane),
  * the Sakoe-Chiba band offset → the sublane axis,
  * anti-diagonals → a sequential fori_loop (2m-1 steps).

All cells of an anti-diagonal depend only on the previous two diagonals,
so every loop step is one dependence-free (B_w, 128) vector op — no
scalar DP.  Without a threshold the loop is a static fori over the band
bound; with one it becomes the early-abandoning PrunedDTW while_loop
(``_kernel_thr``): per-lane data dependence stays banished, the only
data-dependent control is the whole-block exit test (DESIGN.md §3).

Index algebra (r = band radius, u ∈ [0, 2r+2) the band offset):
  diagonal d holds cells (i, j = d - i); we store them at
  u = i - offset_d with offset_d = floor(d/2) - r.  Then
    D[i-1, j]   ← prev1[u]   (d even) / prev1[u-1] (d odd)
    D[i, j-1]   ← prev1[u+1] (d even) / prev1[u]   (d odd)
    D[i-1, j-1] ← prev2[u]   (always)
  and the answer sits at u = r on the final diagonal d = 2m-2.

To avoid in-kernel reversed loads, the wrapper passes candidates
time-REVERSED (and transposed to (time, lane)): x[j] = x_rev[m-1-j] turns
the j-descending gather into a contiguous ascending slice.

VMEM per block: query (m_pad, 1) + candidates (m_pad, 128) + two carry
tiles (B_w, 128)  ≈ 4·(m·129 + 2·B_w·128) bytes — ~1.2 MB at m=2048,
r=128; well inside the ~16 MB v5e VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
BIG = 1e30  # python float: pallas kernels must not capture device constants


def _make_step(q_ref, x_ref, *, m: int, r: int, b_w: int, pad: int):
    """The shared anti-diagonal update: (d, prev1, prev2) -> diagonal d.

    Used verbatim by both kernels — the unconditional fori_loop program
    and the threshold-aware while_loop program — so their per-diagonal
    arithmetic (and hence every completed lane's value) is identical.
    """
    u = jax.lax.broadcasted_iota(jnp.int32, (b_w, LANES), 0)

    def shift_down(a):  # element u <- a[u-1]
        return jnp.concatenate(
            [jnp.full((1, LANES), BIG, a.dtype), a[:-1, :]], axis=0)

    def shift_up(a):    # element u <- a[u+1]
        return jnp.concatenate(
            [a[1:, :], jnp.full((1, LANES), BIG, a.dtype)], axis=0)

    def step(d, prev1, prev2):
        offset = d // 2 - r
        i = offset + u                      # query index of cell u
        j = d - i                           # candidate index of cell u
        # q[i] for u ascending — contiguous slice of the padded query
        q_vals = q_ref[pl.ds(offset + pad, b_w), :]
        # x[j] = x_rev[m-1-j]; ascending in u — contiguous slice
        x_vals = x_ref[pl.ds(m - 1 - d + offset + pad, b_w), :]
        cost = (q_vals - x_vals) ** 2       # (b_w, LANES)

        even = (d % 2) == 0
        top = jnp.where(even, prev1, shift_down(prev1))
        left = jnp.where(even, shift_up(prev1), prev1)
        best = jnp.minimum(jnp.minimum(top, left), prev2)
        best = jnp.where((i == 0) & (j == 0), 0.0, best)
        valid = (i >= 0) & (i < m) & (j >= 0) & (j < m) & \
                (jnp.abs(i - j) <= r)
        return jnp.where(valid, jnp.minimum(cost + best, BIG), BIG)

    return step


def _big_tile(b_w: int) -> jnp.ndarray:
    """A (b_w, LANES) tile of BIG for the loop carries' initial value.

    Built from iotas over both axes rather than as a splat constant:
    Mosaic lays a loop carry out like its initial value, a splat (or a
    one-axis iota) gets a layout replicated along an axis, and the body's
    per-cell result cannot be relaid into that, so the kernel would not
    compile.
    """
    u = jax.lax.broadcasted_iota(jnp.int32, (b_w, LANES), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (b_w, LANES), 1)
    return jnp.where(u + lane >= 0, BIG, 0.0).astype(jnp.float32)


def _kernel(q_ref, x_ref, o_ref, *, m: int, r: int, b_w: int, pad: int):
    step = _make_step(q_ref, x_ref, m=m, r=r, b_w=b_w, pad=pad)

    def body(d, carry):
        prev1, prev2 = carry
        return (step(d, prev1, prev2), prev1)

    init = (_big_tile(b_w), _big_tile(b_w))
    final1, _ = jax.lax.fori_loop(0, 2 * m - 1, body, init)
    o_ref[...] = final1[r, :][None, :]


def _kernel_thr(q_ref, x_ref, t_ref, o_ref, *, m: int, r: int, b_w: int,
                pad: int):
    """Threshold-aware variant: early-abandoning PrunedDTW (arXiv
    2010.05371) on the wavefront.

    Per lane, the minimum over the last *two* anti-diagonals is a sound
    lower bound on the final cost: cell costs are nonnegative and every
    monotone warping path crosses at least one cell of any two adjacent
    anti-diagonals (a diagonal move skips exactly one).  The while_loop
    exits as soon as every lane's bound exceeds its threshold; the
    output applies the shared contract *exact value if DTW <= threshold,
    else BIG* (strict >, so a lane landing exactly on the threshold is
    returned exactly — abandoning can then never drop a top-k member
    whose distance equals the seeded k-th best).
    """
    step = _make_step(q_ref, x_ref, m=m, r=r, b_w=b_w, pad=pad)
    thr = t_ref[...]                        # (1, LANES) per-lane threshold

    def cond(carry):
        d, prev1, prev2 = carry
        bound = jnp.minimum(jnp.min(prev1, axis=0, keepdims=True),
                            jnp.min(prev2, axis=0, keepdims=True))
        # d < 1: the carries still hold the BIG init, not real diagonals
        return (d < 2 * m - 1) & ((d < 1) | jnp.any(bound <= thr))

    def body(carry):
        d, prev1, prev2 = carry
        return (d + 1, step(d, prev1, prev2), prev1)

    init = (0, _big_tile(b_w), _big_tile(b_w))
    _, final1, _ = jax.lax.while_loop(cond, body, init)
    # on early exit final1[r] is a mid-DP cell of a dead lane: >= the
    # lane's bound > thr, so the mask below sends it to BIG as required
    out = final1[r, :][None, :]
    o_ref[...] = jnp.where(out > thr, BIG, out)


def _thr_lanes(threshold, n: int, pad_n: int) -> jnp.ndarray:
    """Per-lane thresholds as a (1, n + pad_n) row.

    Padding lanes get -1.0 — every DP bound is >= 0, so they are dead
    from the first check and can never hold a whole block alive past its
    real lanes' abandon point (with +inf padding a block would always
    run all 2m-1 diagonals).
    """
    thr = jnp.broadcast_to(jnp.asarray(threshold, jnp.float32), (n,))
    return jnp.pad(thr[None, :], ((0, 0), (0, pad_n)),
                   constant_values=-1.0)


@functools.partial(jax.jit, static_argnames=("band", "interpret"))
def dtw_wavefront(query: jnp.ndarray, candidates: jnp.ndarray,
                  band: int, interpret: bool = False,
                  threshold=None) -> jnp.ndarray:
    """Banded squared-DTW: query (m,), candidates (C, m) -> (C,) float32.

    ``band`` is the Sakoe-Chiba radius (use m-1 for unconstrained).
    ``threshold`` (scalar or (C,), broadcast per lane) switches to the
    early-abandoning kernel: lanes return their exact cost when it is
    <= threshold and BIG otherwise, and a 128-lane block stops looping as
    soon as all its lanes are provably over threshold.  ``None`` runs
    the original unconditional program (bit-identical to before).
    """
    c, m = candidates.shape
    assert query.shape[0] == m, "query/candidate lengths must match"
    r = min(band, m - 1)
    b_w = 2 * r + 2
    b_w += (-b_w) % 8                       # sublane alignment
    pad = b_w + 2                           # slack so every ds() is in-bounds

    cp = (-c) % LANES
    # time-reversed, (time, lane) layout, padded both ends
    x_rev = candidates.astype(jnp.float32)[:, ::-1].T       # (m, C)
    x_rev = jnp.pad(x_rev, ((pad, pad), (0, cp)))
    q_pad = jnp.pad(query.astype(jnp.float32)[:, None], ((pad, pad), (0, 0)))

    q_spec = pl.BlockSpec((m + 2 * pad, 1), lambda g: (0, 0))
    x_spec = pl.BlockSpec((m + 2 * pad, LANES), lambda g: (0, g))
    o_spec = pl.BlockSpec((1, LANES), lambda g: (0, g))
    if threshold is None:
        out = pl.pallas_call(
            functools.partial(_kernel, m=m, r=r, b_w=b_w, pad=pad),
            out_shape=jax.ShapeDtypeStruct((1, c + cp), jnp.float32),
            grid=((c + cp) // LANES,),
            in_specs=[q_spec, x_spec],
            out_specs=o_spec,
            interpret=interpret,
        )(q_pad, x_rev)
    else:
        out = pl.pallas_call(
            functools.partial(_kernel_thr, m=m, r=r, b_w=b_w, pad=pad),
            out_shape=jax.ShapeDtypeStruct((1, c + cp), jnp.float32),
            grid=((c + cp) // LANES,),
            in_specs=[q_spec, x_spec, o_spec],
            out_specs=o_spec,
            interpret=interpret,
        )(q_pad, x_rev, _thr_lanes(threshold, c, cp))
    return out[0, :c]


@functools.partial(jax.jit, static_argnames=("band", "interpret"))
def dtw_wavefront_pairs(queries: jnp.ndarray, candidates: jnp.ndarray,
                        band: int, interpret: bool = False,
                        threshold=None) -> jnp.ndarray:
    """Row-aligned banded squared-DTW: (P, m) x (P, m) -> (P,) float32.

    Pair ``p`` gets DTW(queries[p], candidates[p]) — the layout the
    batched re-rank's flattened survivor-pair list needs (each pair may
    have a *different* query).  Reuses the single-query kernel body
    verbatim: the query tile is simply (b_w, LANES) instead of (b_w, 1)
    broadcast, i.e. one query per lane alongside its candidate.  All
    per-lane arithmetic is independent, so pair values are bit-identical
    to ``dtw_wavefront`` with the same (query, candidate) in any lane.
    ``threshold`` (scalar or (P,)) selects the early-abandoning kernel
    with the same exact-or-BIG contract as ``dtw_wavefront``.
    """
    p, m = candidates.shape
    assert queries.shape == candidates.shape, "row-aligned pairs required"
    r = min(band, m - 1)
    b_w = 2 * r + 2
    b_w += (-b_w) % 8                       # sublane alignment
    pad = b_w + 2                           # slack so every ds() is in-bounds

    pp = (-p) % LANES
    # candidates time-reversed, queries in natural time; both (time, lane)
    x_rev = candidates.astype(jnp.float32)[:, ::-1].T       # (m, P)
    x_rev = jnp.pad(x_rev, ((pad, pad), (0, pp)))
    q_t = jnp.pad(queries.astype(jnp.float32).T, ((pad, pad), (0, pp)))

    qx_spec = pl.BlockSpec((m + 2 * pad, LANES), lambda g: (0, g))
    o_spec = pl.BlockSpec((1, LANES), lambda g: (0, g))
    if threshold is None:
        out = pl.pallas_call(
            functools.partial(_kernel, m=m, r=r, b_w=b_w, pad=pad),
            out_shape=jax.ShapeDtypeStruct((1, p + pp), jnp.float32),
            grid=((p + pp) // LANES,),
            in_specs=[qx_spec, qx_spec],
            out_specs=o_spec,
            interpret=interpret,
        )(q_t, x_rev)
    else:
        out = pl.pallas_call(
            functools.partial(_kernel_thr, m=m, r=r, b_w=b_w, pad=pad),
            out_shape=jax.ShapeDtypeStruct((1, p + pp), jnp.float32),
            grid=((p + pp) // LANES,),
            in_specs=[qx_spec, qx_spec, o_spec],
            out_specs=o_spec,
            interpret=interpret,
        )(q_t, x_rev, _thr_lanes(threshold, p, pp))
    return out[0, :p]
