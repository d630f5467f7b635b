"""Mean requests per batch the engine formed in the window (its
``ServingMetrics`` batch-size counts, padding not counted)."""


def read(ctx):
    sizes = ctx["counters"]["batch_sizes"]
    total = sum(sizes.values())
    return sum(k * v for k, v in sizes.items()) / total if total else None
