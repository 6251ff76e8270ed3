"""Device time of latent attention per step: the train step's
``ddal.mla`` scope (``bench/scopes.py``), forward and backward, over
the traced window's steps. Moves ``train_tok_s``."""
import scopes


def read(ctx):
    return scopes.per_step_ms(ctx, "ddal.mla", ctx["counters"].get("steps"))
