"""Roofline share of the fused GEMM kernel (``kernels/fused.py``) over
the executor calls of the traced serving window, at the padded shapes the
kernels are called with."""

import kernels as K
import roofline
import servework


def read(ctx):
    calls = servework.gemm_calls(ctx)
    return roofline.share(ctx, K.FUSED, K.gemm_work(calls), len(calls),
                          "fused_gemm_roofline.decode")
