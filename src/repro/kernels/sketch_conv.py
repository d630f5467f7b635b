"""Pallas TPU kernel — SSH step 1: sliding-window random projections.

The paper slides one Gaussian filter r (length W, stride δ) over each
series and keeps sign bits.  On TPU we tile the series batch over the
sublane axis and the output (window) positions over the lane axis.

Stride-δ windows would need strided VMEM loads (hostile to Mosaic), so the
wrapper performs a **phase decomposition**: the series is laid out as
(δ, B, L) with ``xp[p, b, i] = x[b, i*δ + p]``.  Filter tap w = a·δ + p of
output position t then reads the *contiguous* lane slice
``xp[p, :, t + a : t + a + TN]`` — every tap becomes a shifted
fused-multiply-add on a (TB, TN) tile, unrolled over the W taps (W is a
hyper-parameter, ~30–80).  Arithmetic intensity: W FLOPs per output
element, all operands VMEM-resident.

Mosaic only takes lane-dim loads at offsets it can prove are multiples
of 128, so a tile never loads at ``t + a``.  Instead the same array is
passed ``1 + H`` times: block j (the tile's own lanes) and its H
successors (the halo, H = ceil(((W-1)//δ) / TN)).  The kernel joins them
into one (TB, (1+H)·TN) slab per phase and takes tap a as the static
lane slice ``slab[:, a : a + TN]``.  VMEM per step is O(TB·TN) whatever
the series length, so a whole long stream (``ops.sketch_bits_stream``)
tiles like a batch of short series.

Grid: (B / TB, N_B / TN).  Blocks:
  xp      (δ, TB, TN) × (1 + H) — indices (0, i, j + h), h = 0..H
  filters (W, F)                — whole, in SMEM (scalar taps)
  out     (F, TB, TN)           — index (0, i, j)   (transposed back by wrapper)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TB = 8     # series rows per tile (sublane)
TN = 128   # window positions per tile (lane)


def _kernel(*refs, step: int, window: int, num_f: int):
    *x_refs, f_ref, o_ref = refs               # f_ref: (W, F) in SMEM
    # one (TB, (1+H)·TN) slab per phase: the tile's lanes, then its halo
    slabs = [jnp.concatenate([x[p] for x in x_refs], axis=-1)
             for p in range(step)]
    for f in range(num_f):
        acc = jnp.zeros((TB, TN), jnp.float32)
        for w in range(window):                # static unroll over taps
            a, p = divmod(w, step)
            acc = acc + f_ref[w, f] * slabs[p][:, a:a + TN]
        o_ref[f] = acc


@functools.partial(jax.jit, static_argnames=("step", "interpret"))
def sketch_conv(x: jnp.ndarray, filters: jnp.ndarray, step: int,
                interpret: bool = False) -> jnp.ndarray:
    """Sliding-window projections via Pallas. x (B, m), filters (W, F).

    Returns (B, N_B, F) float32 with N_B = (m - W)//step + 1.
    """
    b, m = x.shape
    window, num_f = filters.shape
    n_b = (m - window) // step + 1

    bp = (-b) % TB
    n_bp = n_b + ((-n_b) % TN)
    halo = -(-((window - 1) // step) // TN)    # halo tiles past each tile
    # phase decomposition: xp[p, b, i] = x[b, i*step + p]
    l = n_bp + halo * TN
    xflat = jnp.pad(x.astype(jnp.float32),
                    ((0, bp), (0, max(l * step - m, 0))))[:, :l * step]
    xp = xflat.reshape(b + bp, l, step).transpose(2, 0, 1)   # (δ, B, L)

    x_specs = [pl.BlockSpec((step, TB, TN),
                            lambda i, j, h=h: (0, i, j + h))
               for h in range(1 + halo)]
    out = pl.pallas_call(
        functools.partial(_kernel, step=step, window=window, num_f=num_f),
        out_shape=jax.ShapeDtypeStruct((num_f, b + bp, n_bp), jnp.float32),
        grid=((b + bp) // TB, n_bp // TN),
        in_specs=x_specs + [pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((num_f, TB, TN), lambda i, j: (0, i, j)),
        interpret=interpret,
    )(*([xp] * (1 + halo)), filters.astype(jnp.float32))
    return out.transpose(1, 2, 0)[:b, :n_b, :]
