"""Roofline share of the fused GEMM kernel (``kernels/fused.py``) on chip
0 over the executor calls of the traced window, at one chip's shapes:
every projection's N and the untied head's vocabulary over
``program.shards``."""

import kernels as K
import roofline
import tpwork as TP


def read(ctx):
    calls = TP.gemm_calls(ctx)
    return roofline.share(ctx, K.FUSED, K.gemm_work(calls), len(calls),
                          "fused_gemm_roofline.tp4")
