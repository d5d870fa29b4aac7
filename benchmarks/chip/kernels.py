"""The kernel calls a step makes, enumerated from the configuration and
the call's shapes, and the patterns that find each kernel's device ops in
the trace (the op names the trace carries; see PERF.md §5).

Operands are counted at the dtypes they are stored in when the kernel
reads them: the program hands every quantised GEMM f32 carriers of its
bf16 values, and the KV arena holds int8 codes with an int32 scale per
page.
"""

from __future__ import annotations

import work as W

# the trace names each kernel's custom call after the jitted function that
# wraps its pallas_call (the head's GEMMs after the autodiff wrapper)
FUSED = r"^%(?:_|jvp_jit__)qmatmul_fused\S* = .*custom-call\("
PAIR = r"^%(?:_|transpose_jvp_jit__)bwd_pair\S* = .*custom-call\("
DECODE_ATTN = r"^%_paged_decode\S* = .*custom-call\("
PREFILL_ATTN = r"^%_flash_prefill_paged\S* = .*custom-call\("

F32 = 4


def _dims(cfg: dict):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    dh = cfg.get("head_dim") or d // h
    return (d, h, cfg["num_key_value_heads"], dh, cfg["intermediate_size"],
            cfg["vocab_size"], cfg["num_hidden_layers"])


def layer_gemms(cfg: dict, m: int) -> list[tuple[int, int, int]]:
    """(M, K, N) of one decoder layer's projections on ``m`` rows."""
    d, h, kv, dh, f, _, _ = _dims(cfg)
    return [(m, d, h * dh), (m, d, kv * dh), (m, d, kv * dh),
            (m, h * dh, d), (m, d, f), (m, d, f), (m, f, d)]


def forward_gemms(cfg: dict, m: int, head_rows: int | None) -> list:
    """Every projection of a forward pass over ``m`` rows, and the output
    head over ``head_rows`` rows (None: no head)."""
    d, *_, v, n = _dims(cfg)
    calls = layer_gemms(cfg, m) * n
    if head_rows:
        calls.append((head_rows, d, v))
    return calls


def gemm_work(calls, *, residual_bytes: int = 0) -> W.Work:
    """Forward kernel work of ``calls``; ``residual_bytes`` per operand
    element when the call also writes quantised operand residuals."""
    total = W.ZERO
    for m, k, n in calls:
        total += W.gemm(m, k, n, a_bytes=F32, b_bytes=F32, out_bytes=F32,
                        extra_out_bytes=residual_bytes * (m * k + k * n))
    return total


def pair_work(calls, *, residual_bytes: int = 1) -> W.Work:
    """Backward-pair work of ``calls`` (the residuals are read packed)."""
    total = W.ZERO
    for t, k, n in calls:
        total += W.bwd_pair(t, k, n, dy_bytes=F32, x_bytes=residual_bytes,
                            w_bytes=residual_bytes, dx_bytes=F32,
                            dw_bytes=F32)
    return total
