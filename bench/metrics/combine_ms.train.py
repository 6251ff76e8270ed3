"""Device time of relevance and the eq. 4 combine per share step: the
train step's ``ddal.combine`` scope (``bench/scopes.py``) over the
traced window's share steps. Moves ``train_tok_s``."""
import scopes


def read(ctx):
    return scopes.per_step_ms(ctx, "ddal.combine",
                              len(ctx["counters"].get("share_s") or ()))
