"""Mean host-clock time of the window's share steps (eq. 4 combine),
batch to fetched metrics. Beside ``local_step_ms.train`` it gives
DDAL's cost of communication. Moves ``train_tok_s``."""


def read(ctx):
    xs = ctx["counters"].get("share_s")
    return 1e3 * sum(xs) / len(xs) if xs else None
