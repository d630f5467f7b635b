"""Device idle share of the traced window, in %: 1 - (union of the
intervals in which an operation ran on the device) / (traced window)."""


def read(ctx):
    tr = ctx["trace"]
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
