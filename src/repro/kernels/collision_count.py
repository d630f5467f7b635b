"""Pallas TPU kernel — LSH signature collision counting (index probe).

The device-side probe replaces hash-bucket pointer chasing (DESIGN.md §3):
for a query signature (K int32 hashes) and the database signature matrix
(N, K), count per-row agreements.  Bandwidth-bound: N·K int32 reads per
probe, so the kernel keeps the query resident and streams the database
through VMEM with candidates on the lane axis.

Layout: the wrapper transposes signatures to (K, N) so each block is
(K_pad, 128) — K on sublanes, candidates on lanes; the count is a sublane
reduction.  Padding rows use disjoint sentinels so they never match.

Grid: (N / 128,).

``collision_count_batch`` is the fused batched-probe variant for the
serving engine (DESIGN.md §4): B query signatures against the same
database in one kernel.  Grid: (N / 128,); each step loads one database
block and counts it against every query of the batch, so the database
streams from HBM once per batch instead of once per query.  The queries
arrive as a resident (L, B_pad, 1) block — one (B_pad, 1) key column per
table — and the count accumulates on a (B_pad, 128) tile, table by
table, so every block obeys the TPU's (8, 128) tiling.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
_DB_SENTINEL = jnp.int32(-2147483648)
_Q_SENTINEL = jnp.int32(2147483647)


def _kernel(q_ref, db_ref, o_ref):
    q = q_ref[...]                                   # (K_pad, 1)
    db = db_ref[...]                                 # (K_pad, LANES)
    eq = (db == q).astype(jnp.int32)
    o_ref[...] = jnp.sum(eq, axis=0, keepdims=True)  # (1, LANES)


@functools.partial(jax.jit, static_argnames=("interpret",))
def collision_count(query_keys: jnp.ndarray, db_keys: jnp.ndarray,
                    interpret: bool = False) -> jnp.ndarray:
    """query (L,), db (N, L) int32 -> (N,) int32 match counts."""
    n, k = db_keys.shape
    kp = (-k) % 8
    np_ = (-n) % LANES
    db = jnp.pad(db_keys.astype(jnp.int32).T, ((0, kp), (0, np_)),
                 constant_values=_DB_SENTINEL)         # (K_pad, N_pad)
    q = jnp.pad(query_keys.astype(jnp.int32)[:, None], ((0, kp), (0, 0)),
                constant_values=_Q_SENTINEL)           # (K_pad, 1)

    out = pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((1, n + np_), jnp.int32),
        grid=((n + np_) // LANES,),
        in_specs=[
            pl.BlockSpec((k + kp, 1), lambda g: (0, 0)),
            pl.BlockSpec((k + kp, LANES), lambda g: (0, g)),
        ],
        out_specs=pl.BlockSpec((1, LANES), lambda g: (0, g)),
        interpret=interpret,
    )(q, db)
    return out[0, :n]


def _batch_kernel(q_ref, db_ref, o_ref):
    db = db_ref[...]                                 # (L, LANES)
    acc = jnp.zeros(o_ref.shape, jnp.int32)          # (B_pad, LANES)
    for k in range(db.shape[0]):                     # static, per table
        acc = acc + (db[k:k + 1, :] == q_ref[k]).astype(jnp.int32)
    o_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("interpret",))
def collision_count_batch(query_keys: jnp.ndarray, db_keys: jnp.ndarray,
                          interpret: bool = False) -> jnp.ndarray:
    """queries (B, L), db (N, L) int32 -> (B, N) int32 match counts.

    Keys on sublanes and candidates on lanes, as in ``collision_count``;
    the batch rides the output's sublanes, padded to a multiple of 8
    (padding query rows are sliced off).
    """
    b, k = query_keys.shape
    n, k2 = db_keys.shape
    assert k == k2, "query/db key widths must match"
    bp = (-b) % 8
    np_ = (-n) % LANES
    db = jnp.pad(db_keys.astype(jnp.int32).T, ((0, 0), (0, np_)),
                 constant_values=_DB_SENTINEL)          # (L, N_pad)
    q = jnp.pad(query_keys.astype(jnp.int32).T, ((0, 0), (0, bp)),
                constant_values=_Q_SENTINEL)[:, :, None]  # (L, B_pad, 1)

    out = pl.pallas_call(
        _batch_kernel,
        out_shape=jax.ShapeDtypeStruct((b + bp, n + np_), jnp.int32),
        grid=((n + np_) // LANES,),
        in_specs=[
            pl.BlockSpec((k, b + bp, 1), lambda g: (0, 0, 0)),
            pl.BlockSpec((k, LANES), lambda g: (0, g)),
        ],
        out_specs=pl.BlockSpec((b + bp, LANES), lambda g: (0, g)),
        interpret=interpret,
    )(q, db)
    return out[:b, :n]
