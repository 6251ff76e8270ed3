"""Device time of the expert layers per step: the train step's
``ddal.moe`` scope with the ``ddal.experts`` scope nested in it
(``bench/scopes.py`` gives each op its innermost scope) and the
grouped products' ragged-dot calls, which the TPU compiler leaves
without a scope (``bench/held_experts.py``): router, dispatch, held
and shared experts and combine, forward and backward, over the traced
window's steps. Moves ``train_tok_s``."""
import held_experts


def read(ctx):
    steps = ctx["counters"].get("steps")
    s = held_experts.scope_s(ctx, held_experts.EXPERT_SCOPES)
    return 1e3 * s / steps if s is not None and steps else None
