"""Device time of the window's gradient sketch per step: the train
step's ``ddal.sketch`` scope (``bench/scopes.py``), the ``sketch_flat``
kernel with the reshapes and relayout copies that feed it, over the
traced window's steps. Moves ``train_tok_s``."""
import scopes


def read(ctx):
    return scopes.per_step_ms(ctx, "ddal.sketch",
                              ctx["counters"].get("steps"))
