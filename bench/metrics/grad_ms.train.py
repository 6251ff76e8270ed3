"""Device time of the agents' forward and backward pass per step: the
train step's ``ddal.grad`` scope (``bench/scopes.py``) over the traced
window's steps. Moves ``train_tok_s``."""
import scopes


def read(ctx):
    return scopes.per_step_ms(ctx, "ddal.grad", ctx["counters"].get("steps"))
