"""Distributed SSH index — the paper's technique as a multi-pod service.

Layout: signatures (N, K) and series (N, m) are row-sharded over EVERY
mesh axis (an index shard per chip).  A query is broadcast; each shard:

  1. counts signature collisions locally           (collision_count kernel)
  2. takes its local top-C/shards candidates       (lax.top_k)
  3. re-ranks them with banded DTW                 (dtw_wavefront kernel)
  4. contributes (dists, global ids) to an all_gather; the global top-k
     is reduced on every chip (k is tiny — replicated reduce is free).

Expressed with ``shard_map`` so the collective schedule is explicit and
auditable: ONE all_gather of k·2 scalars per query — the probe itself is
embarrassingly parallel, preserving SSH's sub-linear DTW count at 512
chips.  Index build is one pass over the local shard (no communication).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.dtw import BIG
from repro.core.index import SSHParams
from repro.db.config import SearchConfig, config_from_legacy_kwargs

def shard_map_nocheck(f, mesh: Mesh, in_specs, out_specs):
    """shard_map with replication checking off."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _signature(series: jnp.ndarray, filters: jnp.ndarray, cws: dict,
               params: SSHParams) -> jnp.ndarray:
    from repro.core import minhash, shingle, sketch
    cwsp = minhash.CWSParams(**cws)
    bits = sketch.sketch_bits(series, filters, params.step)
    counts = shingle.shingle_histogram_batch(bits, params.ngram)
    return minhash.cws_hash_dense_batch(counts, cwsp)


def build_sharded(series: jnp.ndarray, filters: jnp.ndarray, cws: dict,
                  params: SSHParams, mesh: Mesh) -> jnp.ndarray:
    """series (N, m) row-sharded -> signatures (N, K) row-sharded."""
    axes = tuple(mesh.axis_names)
    fn = shard_map_nocheck(
        lambda s: _signature(s, filters, cws, params),
        mesh,
        in_specs=P(axes, None),
        out_specs=P(axes, None))
    return fn(series)


def _make_query_core(encode, mesh: Mesh, config: SearchConfig):
    """The ONE shard-local query schedule, parameterised by a pure
    ``encode(q, state) -> (K,)`` signature fn: local collision scan over
    raw signatures + local top-C/P + local banded DTW, ONE all_gather of
    k·2 scalars per query.  Both public factories delegate here so the
    collective schedule cannot diverge between the legacy and encoder
    entry points.
    """
    if config.band is None:
        raise ValueError("the sharded query fn requires a band radius "
                         "(config.band is None)")
    top_c, band, topk = config.top_c, config.band, config.topk
    backend = config.backend
    abandon = config.use_lb_cascade and config.early_abandon
    axes = tuple(mesh.axis_names)
    n_shards = int(mesh.devices.size)
    local_c = max(topk, top_c // n_shards)

    def local_query(series, sigs, state, q):
        from repro.kernels import ops
        sig = encode(q, state)                                # (K,)
        coll = jnp.sum((sigs == sig[None, :]).astype(jnp.int32), axis=-1)
        hits, cand = jax.lax.top_k(coll, local_c)             # local ids
        cand_series = jnp.take(series, cand, axis=0)
        thr = None
        if abandon:
            # shard-local seed threshold: topk-th best DTW over the
            # first topk hash hits.  Any lane in the shard's true local
            # top-k is <= this bound, and the global k-th is <= every
            # shard's local k-th, so abandoned lanes (exact > thr) can
            # never reach the gathered global top-k — results identical.
            # A shard with fewer than topk hits has no such bound (inf).
            seed = ops.dtw_rerank(q, cand_series[:topk], band,
                                  use_pallas=ops.resolve_backend(backend))
            thr = jnp.where(hits[topk - 1] > 0, jnp.sort(seed)[topk - 1],
                            jnp.inf)
        d = ops.dtw_rerank(q, cand_series, band,
                           use_pallas=ops.resolve_backend(backend),
                           threshold=thr)
        # only rows that collide in at least one hash are candidates, as
        # in the batched and sequential probes; the rest rank as filler
        d = jnp.where(hits > 0, d, BIG)

        shard_id = jax.lax.axis_index(axes)
        n_local = series.shape[0]
        gids = cand + shard_id * n_local
        # gather every shard's (dists, ids); reduce to global top-k
        all_d = jax.lax.all_gather(d, axes, tiled=True)
        all_i = jax.lax.all_gather(gids, axes, tiled=True)
        vals, order = jax.lax.top_k(-all_d, topk)
        return jnp.take(all_i, order), -vals

    return shard_map_nocheck(
        local_query, mesh,
        in_specs=(P(axes, None), P(axes, None), P(), P()),
        out_specs=(P(), P()))


def make_query_fn(params: SSHParams, mesh: Mesh, *, length: int,
                  config: Optional[SearchConfig] = None,
                  top_c: Optional[int] = None, band: Optional[int] = None,
                  topk: Optional[int] = None,
                  backend: Optional[str] = None):
    """Returns query(series_shard, sigs_shard, filters, cws, q) -> (ids, d).

    Canonical form: ``make_query_fn(params, mesh, length=m, config=cfg)``
    — ``cfg.top_c``/``cfg.band``/``cfg.topk`` set the probe and re-rank
    widths, and ``cfg.backend`` selects the shard-local DTW
    implementation via the shared dispatch (``repro.kernels.ops``): the
    Pallas wavefront kernel on TPU, the ``dtw_batch`` scan oracle
    elsewhere — the same knob as the local re-rank pipeline (DESIGN.md
    §3).  A band radius is required (the shard-local re-rank is banded).
    The filter bank and CWS fields stay call-time operands (historical
    signature); the schedule itself is :func:`_make_query_core`.

    Deprecation shim (one release): the loose ``top_c=/band=/topk=/
    backend=`` kwargs still work under a ``DeprecationWarning``.
    """
    if config is None:
        legacy = {k: v for k, v in dict(top_c=top_c, band=band, topk=topk,
                                        backend=backend).items()
                  if v is not None}
        config = config_from_legacy_kwargs("make_query_fn", legacy)
    elif any(v is not None for v in (top_c, band, topk, backend)):
        raise TypeError("make_query_fn() takes either config= or legacy "
                        "top_c/band/topk/backend kwargs, not both")
    if config.band is None:
        raise ValueError("make_query_fn requires a band radius "
                         "(config.band is None)")

    def encode(q, state):
        from repro.core import minhash, shingle, sketch
        from repro.encoders.pipeline import CWSHasher
        cwsp = CWSHasher.cws_params(state)   # one home for the cws/ prefix
        bits = sketch.sketch_bits(q, state["filters"], params.step)
        counts = shingle.shingle_histogram(bits, params.ngram)
        return minhash.cws_hash(counts, cwsp)                 # (K,)

    core = _make_query_core(encode, mesh, config)

    def query(series, sigs, filters, cws, q):
        state = {"filters": filters,
                 **{f"cws/{k}": v for k, v in cws.items()}}
        return core(series, sigs, state, q)

    return query


def make_encoder_query_fn(encoder, mesh: Mesh, *,
                          config: SearchConfig):
    """Encoder-generic twin of :func:`make_query_fn` — the facade path.

    Returns ``query(series_shard, sigs_shard, state, q) -> (ids, dists)``
    where ``state`` is the encoder's materialised array dict
    (``encoder.state()``, replicated).  Any registered encoder whose
    ``pure_encode_fn`` is shard_map-safe serves unchanged — ``"ssh"``,
    ``"srp"``, ``"ssh-multires"``, or out-of-tree.
    """
    return _make_query_core(encoder.pure_encode_fn(), mesh, config)


def index_shardings(mesh: Mesh) -> Tuple[NamedSharding, NamedSharding]:
    axes = tuple(mesh.axis_names)
    return (NamedSharding(mesh, P(axes, None)),
            NamedSharding(mesh, P(axes, None)))
