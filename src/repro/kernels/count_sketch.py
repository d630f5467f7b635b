"""Pallas TPU kernel — batched count-sketch table accumulation.

The streaming shingler ("ssh-cs") replaces the exact F·2^n histogram with
``rows`` signed tables of ``width`` bins; building them is the signature
hot path's scatter.  TPUs have no native scatter-add, so the kernel uses
the one-hot formulation: for a chunk of shingle buckets it materialises
``lane_index == bucket`` over the full table width and reduces the ±1
signs down the sublane axis — a (CHUNK, width) f32 compare+sum that maps
straight onto the VPU, the same trick the collision kernel uses for
sentinel rows.

Hashing stays OUTSIDE the kernel (multiply-shift in uint32 is a handful
of elementwise jnp ops; the scatter is the part worth fusing), so inputs
are pre-hashed buckets (B, R, S) int32 with −1 for padding/masked
shingles and their signs (B, R, S) f32 with 0 at the same slots — the
one-hot compare drops −1 for free since lane indices are non-negative.

Grid: (B, S_pad / CHUNK), chunks innermost so each (1, R, width) output
block stays VMEM-resident while its shingle stream walks through;
``@pl.when(step == 0)`` zero-initialises per b.  The bucket/sign blocks
are (1, R_pad, CHUNK) with R padded to 8 sublanes; the kernel transposes
each to (CHUNK, R_pad) to get one bucket column per table row.  VMEM:
the one-hot block at the default width 4096 is (128, 4096) f32 = 2 MiB
— comfortable against the ~16 MiB budget.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

CHUNK = 128


def _kernel(b_ref, s_ref, o_ref):
    step = pl.program_id(1)

    @pl.when(step == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    _, rows, width = o_ref.shape
    bkt = b_ref[0].T                                 # (CHUNK, R_pad) int32
    sgn = s_ref[0].T                                 # (CHUNK, R_pad) f32
    lanes = jax.lax.broadcasted_iota(jnp.int32, (CHUNK, width), 1)
    for r in range(rows):                            # static, per table row
        hits = jnp.where(lanes == bkt[:, r:r + 1],
                         sgn[:, r:r + 1], 0.0)       # one-hot ±1
        o_ref[0, r:r + 1, :] += jnp.sum(hits, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("width", "interpret"))
def cs_tables(bucket: jnp.ndarray, sign: jnp.ndarray, width: int,
              interpret: bool = False) -> jnp.ndarray:
    """bucket (B, R, S) int32 (−1 invalid), sign (B, R, S) f32 (0 at −1)
    -> (B, R, width) f32 signed count-sketch tables."""
    b, r, s = bucket.shape
    rp = (-r) % 8
    sp = (-s) % CHUNK
    bkt = jnp.pad(bucket.astype(jnp.int32), ((0, 0), (0, rp), (0, sp)),
                  constant_values=-1)
    sgn = jnp.pad(sign.astype(jnp.float32), ((0, 0), (0, rp), (0, sp)))

    in_spec = pl.BlockSpec((1, r + rp, CHUNK), lambda i, c: (i, 0, c))
    return pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((b, r, width), jnp.float32),
        grid=(b, (s + sp) // CHUNK),
        in_specs=[in_spec, in_spec],
        out_specs=pl.BlockSpec((1, r, width), lambda i, c: (i, 0, 0)),
        interpret=interpret,
    )(bkt, sgn)
