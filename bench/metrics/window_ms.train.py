"""Device time of the knowledge window per step: the train step's
``ddal.window`` scope (``bench/scopes.py``), accumulating each step's
gradients and emptying the window on share steps, over the traced
window's steps. Moves ``train_tok_s``."""
import scopes


def read(ctx):
    return scopes.per_step_ms(ctx, "ddal.window",
                              ctx["counters"].get("steps"))
