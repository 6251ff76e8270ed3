"""Share of its roofline that the gradient-sketch kernel
(``kernels/grad_sketch``) reaches in the traced window: the least time
its work could take (``bench/flops.py``) over the time its operations
took on the chip. Moves ``train_tok_s``."""
import flops


def read(ctx):
    t, c = ctx["trace"], ctx["counters"]
    if t is None or not c.get("sketch_calls"):
        return None
    seconds = t.kernel_s(r"sketch")
    if not seconds:
        return None
    w = c["sketch_work"]
    work = {k: v * c["sketch_calls"] for k, v in w.items()}
    return flops.roofline_share(work, seconds, ctx["peaks"])
