"""Pallas TPU kernel — fused flash attention (forward) for the LM cells.

The jnp chunked attention in `repro.models.layers` is what the dry-run
lowers (it shards cleanly under GSPMD); this kernel is the on-chip
replacement for real TPU runs (`use_pallas=True` in ops.dispatch): one
grid cell owns a (q_block × head) tile, loops over KV blocks with the
online-softmax recurrence entirely in VMEM, and writes the normalised
output once — no (S, T) logits ever reach HBM.

Grid: (B·H, S/q_block).  Blocks:
  q   (1, q_block, D)   — index (bh, i)
  k/v (1, T, D)         — whole KV row for the head (VMEM: T·D·4 B;
                          32k × 128 f32 = 16 MB/2 at bf16 — for longer T,
                          extend the grid with a KV ring; documented)
  out (1, q_block, D)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, *, kv_block: int, t: int,
            causal: bool, scale: float, q_block: int):
    i = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale          # (qb, D)
    qb, d = q.shape
    n_kv = t // kv_block

    def body(j, carry):
        m, l, acc = carry
        k_blk = k_ref[0, pl.ds(j * kv_block, kv_block), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(j * kv_block, kv_block), :].astype(jnp.float32)
        logits = q @ k_blk.T                          # (qb, kvb)
        if causal:
            q_pos = i * q_block + jax.lax.broadcasted_iota(
                jnp.int32, (qb, kv_block), 0)
            k_pos = j * kv_block + jax.lax.broadcasted_iota(
                jnp.int32, (qb, kv_block), 1)
            logits = jnp.where(q_pos >= k_pos, logits, NEG_INF)
        m2 = jnp.maximum(m, jnp.max(logits, axis=-1))
        p = jnp.exp(logits - m2[:, None])
        corr = jnp.exp(m - m2)
        l2 = l * corr + jnp.sum(p, axis=-1)
        acc2 = acc * corr[:, None] + p @ v_blk
        return m2, l2, acc2

    init = (jnp.full((qb,), NEG_INF, jnp.float32),
            jnp.zeros((qb,), jnp.float32),
            jnp.zeros((qb, d), jnp.float32))
    m, l, acc = jax.lax.fori_loop(0, n_kv, body, init)
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("causal", "q_block", "kv_block",
                                    "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = True, q_block: int = 128,
                    kv_block: int = 128,
                    interpret: bool = False) -> jnp.ndarray:
    """q (B, H, S, D), k/v (B, H, T, D) -> (B, H, S, D).

    MQA/GQA callers repeat KV heads before the call (cheap view).
    """
    b, h, s, d = q.shape
    t = k.shape[2]
    scale = d ** -0.5
    qb = min(q_block, s)
    kvb = min(kv_block, t)
    sp, tp = (-s) % qb, (-t) % kvb
    # pad: padded K positions get masked by causality only if causal;
    # for the non-causal case pad K with -inf-producing zeros + mask via
    # extra causal-style bound — simplest: require divisibility after pad
    # and mask padded keys through position comparison below.
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, sp), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, tp), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, tp), (0, 0)))
    if tp and not causal:
        raise ValueError("non-causal flash requires T divisible by "
                         f"kv_block (got T={t}, kv_block={kvb})")
    sq, st = s + sp, t + tp

    q3 = qp.reshape(b * h, sq, d)
    k3 = kp.reshape(b * h, st, d)
    v3 = vp.reshape(b * h, st, d)

    out = pl.pallas_call(
        functools.partial(_kernel, kv_block=kvb, t=st, causal=causal,
                          scale=scale, q_block=qb),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        grid=(b * h, sq // qb),
        in_specs=[
            pl.BlockSpec((1, qb, d), lambda bh, i: (bh, i, 0)),
            pl.BlockSpec((1, st, d), lambda bh, i: (bh, 0, 0)),
            pl.BlockSpec((1, st, d), lambda bh, i: (bh, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, qb, d), lambda bh, i: (bh, i, 0)),
        interpret=interpret,
    )(q3, k3, v3)
    return out.reshape(b, h, sq, d)[:, :, :s]
