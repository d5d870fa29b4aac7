"""Roofline share of the backward-pair kernel (``kernels/bwd_pair.py``,
dx and dw of one GEMM in one call) over the traced training steps."""

import kernels as K
import roofline


def read(ctx):
    c, cfg = ctx["counters"], ctx["config"]
    t = c["batch"] * c["seq"]
    per_step = K.forward_gemms(cfg, t, t)
    steps = c["traced_steps"]
    work = K.pair_work(per_step).scaled(steps)
    return roofline.share(ctx, K.PAIR, work, len(per_step) * steps,
                          "bwd_pair_roofline.train")
