"""Mean host-clock time of the window's local (accumulate-only) steps,
batch to fetched metrics. Moves ``train_tok_s``."""


def read(ctx):
    xs = ctx["counters"].get("local_s")
    return 1e3 * sum(xs) / len(xs) if xs else None
