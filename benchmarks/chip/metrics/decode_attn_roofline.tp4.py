"""Roofline share of ``paged_attn_decode`` on chip 0 over the traced
decode calls, at one chip's work: its query heads and KV heads (the
configuration's heads over ``program.shards``), one op per layer per
call."""

import kernels as K
import roofline
import tpwork as TP


def read(ctx):
    work, calls = TP.decode_attention(ctx)
    return roofline.share(ctx, K.DECODE_ATTN, work, calls,
                          "decode_attn_roofline.tp4")
