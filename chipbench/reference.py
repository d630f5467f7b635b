"""Plain reference of SSH search (Luo & Shrivastava, arXiv:1610.07328),
written from the paper's description and imported from nothing in
``src/``.

For one query it computes what the served top-k has to be:

1. the encoder's random fields, drawn from the configuration's seed by
   the stated key schedule (``PRNGKey(seed)`` split into the filter key
   and the CWS key; the CWS key split five ways into u1, u2, v1, v2 and
   beta; r = -log u1 - log u2, c = -log v1 - log v2);
2. every archive row's signature: sign bits of the filter slid at step
   delta (taps summed in order), the histogram of its n-bit shingles,
   and 0-bit consistent weighted sampling over that histogram, one hash
   per CWS field row;
3. the query's signature at each of ``multiprobe_offsets`` shifts (the
   query cut to ``q[o:]``), and each row's collision count: the most
   signature positions it shares with any shift;
4. the ``top_c`` rows by count, ties to the lower row id, rows sharing
   nothing left out (all ``top_c`` first rows when none shares
   anything);
5. banded squared DTW (Sakoe-Chiba radius ``band``) of the query against
   each of them, by the plain row-by-row recurrence, and the ``topk``
   smallest.

``dtype`` sets the precision of every floating-point step; the control
runs it in bfloat16, one step below the configuration's float32.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

INF = float("inf")


class Reference:
    def __init__(self, encoder: dict, search: dict,
                 dtype=jnp.float32, chunk: int = 512,
                 pair_block: int = 16384):
        if not search.get("rank_by_signature", True):
            raise ValueError("the reference ranks by whole signatures only")
        self.window = int(encoder["window"])
        self.step = int(encoder["step"])
        self.ngram = int(encoder["ngram"])
        self.num_filters = int(encoder.get("num_filters", 1))
        self.num_hashes = int(encoder["num_hashes"])
        self.seed = int(encoder["seed"])
        self.topk = int(search["topk"])
        self.top_c = int(search["top_c"])
        self.band = int(search["band"])
        self.offsets = int(search.get("multiprobe_offsets", 1))
        self.dtype = dtype
        self.chunk = chunk
        self.pair_block = pair_block
        self.dim = self.num_filters << self.ngram
        self._fields()

    # -- 1. random fields -------------------------------------------------
    def _fields(self) -> None:
        key = jax.random.PRNGKey(self.seed)
        kf, kc = jax.random.split(key)
        self.filters = jax.random.normal(
            kf, (self.window, self.num_filters), jnp.float32)
        k1, k2, k3, k4, k5 = jax.random.split(kc, 5)
        shape = (self.num_hashes, self.dim)
        u1 = jax.random.uniform(k1, shape, jnp.float32, 1e-12, 1.0)
        u2 = jax.random.uniform(k2, shape, jnp.float32, 1e-12, 1.0)
        v1 = jax.random.uniform(k3, shape, jnp.float32, 1e-12, 1.0)
        v2 = jax.random.uniform(k4, shape, jnp.float32, 1e-12, 1.0)
        self.r = -jnp.log(u1) - jnp.log(u2)
        self.log_c = jnp.log(-jnp.log(v1) - jnp.log(v2))
        self.beta = jax.random.uniform(k5, shape, jnp.float32)

    # -- 2. signatures ----------------------------------------------------
    def signatures(self, rows: jnp.ndarray) -> jnp.ndarray:
        """(N, m) series -> (N, K) int32 signatures, in row chunks."""
        n = int(rows.shape[0])
        out = []
        for lo in range(0, n, self.chunk):
            block = rows[lo:lo + self.chunk]
            pad = self.chunk - int(block.shape[0])
            if pad:
                block = jnp.pad(block, ((0, pad), (0, 0)))
            sig = _encode(block, self.filters, self.r, self.log_c,
                          self.beta, step=self.step, ngram=self.ngram,
                          dtype=self.dtype)
            out.append(sig[:self.chunk - pad])
        return jnp.concatenate(out, axis=0)

    def query_signatures(self, q: np.ndarray) -> jnp.ndarray:
        """(O, K): the signature of ``q[o:]`` for each shift o."""
        return jnp.stack([
            _encode(jnp.asarray(q[None, o:]), self.filters, self.r,
                    self.log_c, self.beta, step=self.step,
                    ngram=self.ngram, dtype=self.dtype)[0]
            for o in range(self.offsets)])

    # -- 3./4. candidates -------------------------------------------------
    def candidates(self, db_sigs: jnp.ndarray, q_sigs: jnp.ndarray
                   ) -> Tuple[np.ndarray, np.ndarray]:
        """(S, O, K) query signatures -> ids (S, C), valid (S, C)."""
        n = int(db_sigs.shape[0])
        c = min(self.top_c, n)
        counts = _counts(db_sigs, q_sigs)                  # (S, N)
        order = jnp.argsort(-counts, axis=1, stable=True)[:, :c]
        top = jnp.take_along_axis(counts, order, axis=1)
        ids, valid = np.array(order), np.asarray(top) > 0
        empty = ~valid.any(axis=1)
        ids[empty] = np.arange(c)
        valid[empty] = True
        return ids, valid

    # -- 5. DTW and the top-k ----------------------------------------------
    def dtw(self, queries: np.ndarray, rows: jnp.ndarray) -> np.ndarray:
        """Row-aligned banded squared DTW, (P, m) x (P, m) -> (P,)."""
        return np.asarray(_dtw_pairs(jnp.asarray(queries, self.dtype),
                                     jnp.asarray(rows, self.dtype),
                                     band=self.band), np.float64)

    def search_as_served(self, archive: jnp.ndarray, db_sigs: jnp.ndarray,
                         queries: np.ndarray, served_ids):
        """Reference top-k of each query, its tie group at the top-C
        cut resolved as the served list resolved it.

        SSH takes the ``top_c`` rows by collision count; which rows of
        the group tied at the cut are taken is not part of it.  Every row
        above the cut is a candidate.  Of the tied group, the candidates
        are the tied rows the served list names, and the rest of the
        group's places go to tied rows whose DTW is no better than the
        k-th best so far; only where too few such rows exist do better
        ones fill the places (the worst of them first).  A count of zero
        makes no candidate; where no row shares anything, the first
        ``top_c`` rows are the candidates.

        Returns (ids, dists, foreign): lists of (k,) arrays, and per
        query the served ids that no resolution of the tie makes a
        candidate (a row under the cut, or more tied rows than the
        group has places).
        """
        q_sigs = jnp.stack([self.query_signatures(q) for q in queries])
        counts = np.asarray(_counts(db_sigs, q_sigs))        # (S, N)
        n = counts.shape[1]
        c = min(self.top_c, n)
        bases, rests, needs, foreign = [], [], [], []
        for cnt, served in zip(counts, served_ids):
            served = np.asarray(served, np.int64)
            served = served[(served >= 0) & (served < n)]
            cut = int(np.partition(cnt, n - c)[n - c])
            if cut == 0:
                base = np.nonzero(cnt > 0)[0]
                if not len(base):
                    base = np.arange(c)
                rest, need = np.zeros(0, np.int64), 0
                bad = int(np.sum(~np.isin(served, base)))
            else:
                above = np.nonzero(cnt > cut)[0]
                tie = np.nonzero(cnt == cut)[0]
                places = c - len(above)
                named = served[cnt[served] == cut]
                bad = int(np.sum(cnt[served] < cut)) + max(
                    0, len(named) - places)
                named = named[:places]
                base = np.concatenate([above, named])
                rest = np.setdiff1d(tie, named)
                need = places - len(named)
            bases.append(base)
            rests.append(rest)
            needs.append(need)
            foreign.append(bad)
        d_base = self._pair_dtw(archive, queries, bases)
        k = min(self.topk, c)
        kth = [np.sort(d)[k - 1] if len(d) >= k else INF for d in d_base]
        extra = self._fill(archive, queries, rests, needs, kth)
        ids, dists = [], []
        for base, d, (e_ids, e_d) in zip(bases, d_base, extra):
            all_ids = np.concatenate([base, e_ids])
            all_d = np.concatenate([d, e_d])
            pos = np.lexsort((all_ids, all_d))[:k]
            ids.append(all_ids[pos])
            dists.append(all_d[pos])
        return ids, dists, np.asarray(foreign)

    def _fill(self, archive, queries, rests, needs, kth, step: int = 512):
        """Per query, the tied rows better than ``kth`` that have to take
        some of its ``need`` remaining places, because too few tied rows
        no better than ``kth`` are left: [(ids, dists), ...].  The group
        is read ``step`` rows at a time, all queries together, until each
        has found enough rows no better than ``kth``."""
        s_n = len(rests)
        harmless = [0] * s_n
        better = [([], []) for _ in range(s_n)]
        open_ = [i for i in range(s_n) if needs[i] > 0 and len(rests[i])]
        lo = 0
        while open_:
            parts = [rests[i][lo:lo + step] for i in open_]
            ds = self._pair_dtw(archive, np.asarray(queries)[open_], parts)
            still = []
            for i, part, d in zip(open_, parts, ds):
                harmless[i] += int(np.sum(d >= kth[i]))
                better[i][0].append(part[d < kth[i]])
                better[i][1].append(d[d < kth[i]])
                if harmless[i] < needs[i] and lo + step < len(rests[i]):
                    still.append(i)
            open_, lo = still, lo + step
        out = []
        for i in range(s_n):
            short = needs[i] - harmless[i]
            if short <= 0 or not better[i][0]:
                out.append((np.zeros(0, np.int64), np.zeros(0)))
                continue
            b_ids = np.concatenate(better[i][0])
            b_d = np.concatenate(better[i][1])
            take = np.argsort(-b_d, kind="stable")[:short]
            out.append((b_ids[take], b_d[take]))
        return out

    def _pair_dtw(self, archive, queries: np.ndarray, row_sets):
        """DTW of query s against each row of ``row_sets[s]``, in blocks
        of ``pair_block`` pairs (one compiled shape)."""
        lens = [len(r) for r in row_sets]
        q_idx = np.repeat(np.arange(len(row_sets)), lens)
        r_idx = (np.concatenate(row_sets).astype(np.int64) if sum(lens)
                 else np.zeros(0, np.int64))
        p, blk = len(r_idx), self.pair_block
        pad = (-p) % blk
        q_idx = np.concatenate([q_idx, np.zeros(pad, np.int64)])
        r_idx = np.concatenate([r_idx, np.zeros(pad, np.int64)])
        q = np.asarray(queries)
        out = [self.dtw(q[q_idx[lo:lo + blk]],
                        archive[jnp.asarray(r_idx[lo:lo + blk])])
               for lo in range(0, p + pad, blk)]
        d = np.concatenate(out)[:p] if out else np.zeros(0)
        return np.split(d, np.cumsum(lens)[:-1])

    def search(self, archive: jnp.ndarray, db_sigs: jnp.ndarray,
               queries: np.ndarray):
        """Reference top-k of each query: (ids (S, k), dists (S, k))."""
        q_sigs = jnp.stack([self.query_signatures(q) for q in queries])
        ids, valid = self.candidates(db_sigs, q_sigs)
        s, c = ids.shape
        q_rows = np.repeat(np.asarray(queries), c, axis=0)
        d = self.dtw(q_rows, archive[jnp.asarray(ids.reshape(-1))])
        d = np.where(valid.reshape(-1), d, INF).reshape(s, c)
        k = min(self.topk, c)
        pos = np.argsort(d, axis=1, kind="stable")[:, :k]
        out_d = np.take_along_axis(d, pos, axis=1)
        out_ids = np.take_along_axis(ids, pos, axis=1)
        return out_ids, out_d


@functools.partial(jax.jit, static_argnames=("step", "ngram", "dtype"))
def _encode(x, filters, r, log_c, beta, *, step: int, ngram: int, dtype):
    """(R, m) -> (R, K) signatures (paper Fig. 5: sketch, shingle, CWS)."""
    x = x.astype(dtype)
    filters = filters.astype(dtype)
    window, num_f = filters.shape
    rows, m = x.shape
    n_b = (m - window) // step + 1
    span = step * (n_b - 1) + 1
    bits = []
    for f in range(num_f):
        acc = jnp.zeros((rows, n_b), dtype)
        for w in range(window):                    # taps summed in order
            acc = acc + filters[w, f] * x[:, w:w + span:step]
        bits.append((acc >= 0).astype(jnp.int32))
    n_sh = n_b - ngram + 1
    ids = []
    for f, b in enumerate(bits):
        sid = jnp.zeros((rows, n_sh), jnp.int32)
        for j in range(ngram):
            sid = sid + (b[:, j:j + n_sh] << j)
        ids.append(sid + (f << ngram))
    ids = jnp.concatenate(ids, axis=1)             # (R, F * n_sh)
    dim = r.shape[1]
    hist = jnp.zeros((rows, dim), jnp.int32).at[
        jnp.arange(rows)[:, None], ids].add(1)
    # every bin of the shingle space, inactive bins out by +inf; the
    # least value, and among bins that reach it the lowest bin
    active = hist > 0
    logw = jnp.where(active, jnp.log(jnp.maximum(hist, 1).astype(dtype)),
                     0).astype(dtype)
    r, log_c, beta = (a.astype(dtype) for a in (r, log_c, beta))

    def one_hash(fields):
        rk, lck, bk = fields                       # (D,)
        t = jnp.floor(logw / rk + bk)
        ln_a = lck - rk * (t - bk) - rk
        ln_a = jnp.where(active, ln_a, jnp.inf)
        return jnp.argmin(ln_a, axis=1).astype(jnp.int32)

    return jax.lax.map(one_hash, (r, log_c, beta)).T


@jax.jit
def _counts(db_sigs, q_sigs):
    """(N, K), (S, O, K) -> (S, N): the most positions any shift shares."""
    def one(qs):
        per = jnp.sum((db_sigs[None, :, :] == qs[:, None, :]).astype(
            jnp.int32), axis=2)                             # (O, N)
        return per.max(axis=0)
    return jax.lax.map(one, q_sigs)


@functools.partial(jax.jit, static_argnames=("band",))
def _dtw_pairs(q, x, band: int):
    """D[i][j] = (q_i - x_j)^2 + min(D[i-1][j-1], D[i-1][j], D[i][j-1])
    over |i - j| <= band, one row of the band at a time; the answer is
    D[m-1][m-1].  Row i holds cells j = i + k - band, k in [0, 2*band]."""
    p, m = q.shape
    width = 2 * band + 1
    big = jnp.asarray(INF, q.dtype)
    xp = jnp.pad(x, ((0, 0), (band, band)))             # xp[:, j + band]
    k = jnp.arange(width)

    def row(prev, i):
        j = i + k - band                                 # (W,)
        ok = (j >= 0) & (j < m)
        xi = jax.lax.dynamic_slice(xp, (0, i), (p, width)).T      # (W, P)
        cost = (q[:, i][None, :] - xi) ** 2
        up = jnp.concatenate([prev[1:], jnp.full((1, p), big)], axis=0)
        above = cost + jnp.minimum(prev, up)             # from row i - 1

        def cell(left, inp):
            a, c, valid = inp
            d = jnp.where(valid, jnp.minimum(a, left + c), big)
            return d, d

        _, cur = jax.lax.scan(cell, jnp.full((p,), big),
                              (above, cost, ok))
        return cur, None

    prev0 = jnp.full((width, p), big).at[band].set(0)   # D[-1][-1] = 0
    last, _ = jax.lax.scan(row, prev0, jnp.arange(m))
    return last[band]
