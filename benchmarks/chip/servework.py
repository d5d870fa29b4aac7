"""Work of the serving executor's calls in a traced window, from the
counters the serving driver records around each call."""

from __future__ import annotations

import kernels as K
import work as W


def model_flops(ctx) -> float:
    cfg, c = ctx["config"], ctx["counters"]
    total = 0.0
    for _, rows, lens in c.get("decodes", []):
        total += sum(W.serve_flops_per_token(cfg, n) for n in lens[:rows])
    for _, t0, q_len, _, _ in c.get("prefills", []):
        # token i of the slab sees t0 + i + 1 tokens
        total += q_len * 2.0 * W.param_count(cfg)["matmul"] + \
            W.attention_flops_per_token(cfg, t0 + (q_len + 1) / 2.0) * q_len
    return total


def mfu(ctx) -> float | None:
    flat = ctx["trace"]
    if ctx["peak"] is None or not flat["window"]:
        return None
    flops = model_flops(ctx)
    if flops == 0:
        return None
    window_s = (flat["window"][1] - flat["window"][0]) / 1e9
    return 100.0 * flops / window_s / ctx["peak"]["bf16_flops_per_s"]


def gemm_calls(ctx) -> list:
    """(M, K, N) of every quantised GEMM the traced calls ran, at the
    padded shapes the kernels are called with."""
    cfg, c = ctx["config"], ctx["counters"]
    calls = []
    for _ in c.get("decodes", []):
        calls += K.forward_gemms(cfg, c["max_batch"], c["max_batch"])
    for _, _, _, width, final in c.get("prefills", []):
        calls += K.forward_gemms(cfg, width, 1 if final else None)
    return calls
