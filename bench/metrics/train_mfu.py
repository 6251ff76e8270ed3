"""Model-FLOP utilization of the whole train step: the model's
operations per token (``bench/flops.py``, no recomputation) times the
tokens per second of the traced window, over the chips' bf16 peak.
Moves ``train_tok_s``."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("tokens") or "train_flops_per_token" not in c:
        return None
    rate = c["tokens"] / ctx["window_s"]
    return (100.0 * c["train_flops_per_token"] * rate
            / (ctx["chips"] * ctx["peaks"]["bf16_flops_s"]))
