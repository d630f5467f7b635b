"""FleetWorker — one logical worker's shard replicas + shard-local query.

The shard-local math mirrors ``distributed.dist_index._make_query_core``
exactly (collision scan over raw signatures → local top-C/S → shard-seed
threshold → banded early-abandoning DTW), so the fleet tier preserves
SSH's sub-linear DTW count per shard.  Workers holding replicas of the
same shard fetched the same checkpoint artifact, so the same (sig, q)
input yields bit-identical (ids, dists) on every replica — which is why
hedged / failed-over queries answer identically to the healthy run.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dtw import BIG


@dataclasses.dataclass
class ShardReplica:
    """One shard's encoded rows as held by a worker."""
    series: jnp.ndarray        # (n_s, m)
    signatures: jnp.ndarray    # (n_s, K)
    row_start: int             # global id of local row 0

    @property
    def n_rows(self) -> int:
        return int(self.signatures.shape[0])

    def nbytes(self) -> int:
        return int(np.asarray(self.series).nbytes
                   + np.asarray(self.signatures).nbytes)


class FleetWorker:
    """A logical worker: named, holds shard replicas, answers shard
    queries.  Thread-safe for the fleet's concurrent fan-out (shard
    loads/drops take the lock; queries read a stable snapshot)."""

    def __init__(self, name: str):
        self.name = name
        self._lock = threading.Lock()
        self._shards: Dict[int, ShardReplica] = {}

    # -- shard custody ----------------------------------------------------
    def receive_shard(self, shard_id: int, replica: ShardReplica) -> None:
        with self._lock:
            self._shards[shard_id] = replica

    def drop_shard(self, shard_id: int) -> None:
        with self._lock:
            self._shards.pop(shard_id, None)

    def shard_ids(self):
        with self._lock:
            return sorted(self._shards)

    def nbytes(self) -> int:
        with self._lock:
            return sum(r.nbytes() for r in self._shards.values())

    # -- the shard-local query (dist_index schedule, host-orchestrated) ---
    def query_shard(self, shard_id: int, sig: jnp.ndarray, q: jnp.ndarray,
                    *, local_c: int, topk: int, band: int,
                    use_pallas: Optional[bool], abandon: bool,
                    injector=None) -> Tuple[np.ndarray, np.ndarray]:
        """(global ids, dists) of this shard's local top-``local_c``.

        Deterministic in (shard state, sig, q): any replica of the same
        artifact returns bit-identical arrays.  ``injector`` (a
        ``FaultInjector``) gates the call for chaos tests/benchmarks.
        """
        if injector is not None:
            injector.before_call(self.name)
        with self._lock:
            try:
                rep = self._shards[shard_id]
            except KeyError:
                raise KeyError(f"worker {self.name!r} holds no replica "
                               f"of shard {shard_id}") from None
        from repro.kernels import ops
        c = min(local_c, rep.n_rows)
        coll = jnp.sum((rep.signatures == sig[None, :]).astype(jnp.int32),
                       axis=-1)
        hits, cand = jax.lax.top_k(coll, c)
        cand_series = jnp.take(rep.series, cand, axis=0)
        thr = None
        if abandon and c > topk and int(hits[topk - 1]) > 0:
            # shard-local seed threshold (same soundness argument as the
            # shard_map path): the global k-th best is <= this shard's
            # k-th best over its first topk hash hits, so a lane the
            # threshold abandons can never reach the merged top-k
            seed = ops.dtw_rerank(q, cand_series[:topk], band,
                                  use_pallas=use_pallas)
            thr = jnp.sort(seed)[topk - 1]
        d = ops.dtw_rerank(q, cand_series, band, use_pallas=use_pallas,
                           threshold=thr)
        # rows colliding in no hash are not candidates (as in dist_index)
        d = jnp.where(hits > 0, d, BIG)
        gids = np.asarray(cand, np.int64) + rep.row_start
        return gids, np.asarray(d, np.float32)
