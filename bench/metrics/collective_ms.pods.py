"""Collective time per step that no other operation on the chip
overlaps (``TraceSummary.collective_exposed_s``, averaged over the
chips): the share step's intra-pod gathers and leader exchange, and
the relevance sketches' gather, over the traced window's steps. Moves
``train_tok_s``."""


def read(ctx):
    t, steps = ctx["trace"], ctx["counters"].get("steps")
    if t is None or not steps:
        return None
    return 1e3 * t.collective_exposed_s / steps
