"""Device time of the optimizer per step: the train step's
``ddal.optimizer`` scope (``bench/scopes.py``), the choice of gradient,
clipping, AdamW and the row selects, over the traced window's steps.
Moves ``train_tok_s``."""
import scopes


def read(ctx):
    return scopes.per_step_ms(ctx, "ddal.optimizer",
                              ctx["counters"].get("steps"))
