"""Mean of ``SearchResult.n_candidates`` over the window's requests: the
(query, candidate) pairs the LB cascade left for DTW, a count."""


def read(ctx):
    n = ctx["counters"]["n_candidates"]
    return sum(n) / len(n) if n else None
