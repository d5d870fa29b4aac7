"""Model FLOP/s utilisation of serving over the traced ticks: 2 FLOPs per
matmul weight plus attention over its context, for every token the
executor processed (decode rows and prefill rows), over the traced window
and the chip's bf16 peak, in %."""

import servework


def read(ctx):
    return servework.mfu(ctx)
