"""Roofline share of ``paged_attn_decode`` over the traced decode calls,
one op per layer per call; the bytes are the int8 pages and per-page
scales the rows read, with the queries and outputs."""

import kernels as K
import roofline
import work as W


def read(ctx):
    cfg, c = ctx["config"], ctx["counters"]
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    n = cfg["num_hidden_layers"]
    total = W.ZERO
    for _, rows, lens in c.get("decodes", []):
        total += W.decode_attention(
            lens[:rows], heads=h, kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg.get("head_dim") or d // h,
            page_size=c["page_size"]).scaled(n)
    return roofline.share(ctx, K.DECODE_ATTN, total,
                          len(c.get("decodes", [])) * n,
                          "decode_attn_roofline")
