"""Whole decode step: operations of every traced decode step (weights at
the held layers' widths, the head, attention over each slot's live
context; ``bench.costs.decode_flops``) over the traced window's seconds
and the chip's peak bf16 FLOP/s, %."""


def read(ctx):
    from bench import costs
    t, info = ctx["trace"], ctx["traced"]
    if not t or not info.get("contexts") or t["window_s"] <= 0:
        return None
    flops = costs.decode_flops(info["contexts"], ctx["dims"])
    return 100.0 * flops / t["window_s"] / ctx["peaks"]["bf16_flops_per_s"]
