"""Model FLOP/s utilisation of training: model FLOPs per token (6 per
matmul weight plus attention, recompute not counted) times the window's
tokens per second, over the chip's bf16 peak, in %."""

from work import train_flops_per_token


def read(ctx):
    c = ctx["counters"]
    if not c.get("tokens_per_s") or ctx["peak"] is None:
        return None
    flops = train_flops_per_token(ctx["config"], c["seq"]) * c["tokens_per_s"]
    return 100.0 * flops / ctx["peak"]["bf16_flops_per_s"]
