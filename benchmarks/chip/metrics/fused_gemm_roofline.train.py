"""Roofline share of the fused forward GEMM kernel (``kernels/fused.py``)
over the traced training steps: every projection and the head, and the
layers' projections once more for the recompute of remat."""

import kernels as K
import roofline


def read(ctx):
    c, cfg = ctx["counters"], ctx["config"]
    t = c["batch"] * c["seq"]
    per_step = K.forward_gemms(cfg, t, t) + K.forward_gemms(cfg, t, None)
    steps = c["traced_steps"]
    work = K.gemm_work(per_step, residual_bytes=1).scaled(steps)
    return roofline.share(ctx, K.FUSED, work, len(per_step) * steps,
                          "fused_gemm_roofline.train")
