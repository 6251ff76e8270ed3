"""Share of their roofline that the held experts' grouped products
reach in the traced window: the least time their work could take, from
the token-expert pairs the program counted (``bench/kanana_flops.py``),
over the device time of the train step's ``ddal.experts`` scope and
the ragged-dot calls the TPU compiler leaves without a scope
(``bench/held_experts.py``), one chip's share of each. Moves
``train_tok_s``."""
import flops
import held_experts


def read(ctx):
    work = ctx["counters"].get("expert_work")
    s = held_experts.scope_s(ctx, ("ddal.experts",))
    if not work or not s:
        return None
    return flops.roofline_share(work, s, ctx["peaks"])
