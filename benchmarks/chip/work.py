"""Operations and bytes of each kernel call, computed from its shapes, and
the model FLOPs per token that utilisation is measured against.

These are the benchmark's own yardstick: nothing here is imported from the
program.  A kernel's work is what its algorithm needs: 2*M*N*K for a
matrix product (in-kernel quantisation and multi-pass f32 contractions
are not counted), and the bytes of its operands and outputs at the dtypes
they are stored in.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def scaled(self, n: float) -> "Work":
        return Work(self.flops * n, self.bytes * n)


ZERO = Work(0.0, 0.0)


def peaks(device_kind: str, path: str | None = None) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an error,
    never a default."""
    with open(path or os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(table)})")
    return table[device_kind]


def roofline_s(work: Work, peak: dict) -> tuple[float, str]:
    """Least time the chip could take for ``work``, and what bounds it."""
    t_c = work.flops / peak["bf16_flops_per_s"]
    t_m = work.bytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "bandwidth")


# ------------------------------- kernels -----------------------------------


def gemm(m: int, k: int, n: int, *, a_bytes: int, b_bytes: int,
         out_bytes: int, extra_out_bytes: float = 0.0) -> Work:
    """C[m, n] = A[m, k] @ B[k, n]; ``extra_out_bytes`` are further outputs
    the call writes (the quantised residuals a training forward keeps)."""
    return Work(2.0 * m * k * n,
                float(m * k * a_bytes + k * n * b_bytes + m * n * out_bytes)
                + extra_out_bytes)


def bwd_pair(t: int, k: int, n: int, *, dy_bytes: int, x_bytes: int,
             w_bytes: int, dx_bytes: int, dw_bytes: int) -> Work:
    """One backward pair of y[t, n] = x[t, k] @ w[k, n]: dx = dy @ w^T and
    dw = x^T @ dy in one call."""
    return Work(4.0 * t * k * n,
                float(t * n * dy_bytes + t * k * x_bytes + k * n * w_bytes
                      + t * k * dx_bytes + k * n * dw_bytes))


def decode_attention(ctx_lens, *, heads: int, kv_heads: int, head_dim: int,
                     page_size: int, code_bytes: int = 1,
                     scale_bytes: int = 4, q_bytes: int = 4,
                     o_bytes: int = 4) -> Work:
    """Paged decode attention of one query token per row over its own
    context: QK^T and PV are 2*ctx*head_dim each per head; the bytes are
    the packed K and V pages the rows read, with one scale per page of
    each, plus the queries and outputs."""
    flops = 0.0
    nbytes = 0.0
    for c in ctx_lens:
        if c <= 0:
            continue
        pages = -(-c // page_size)
        flops += 4.0 * heads * head_dim * c
        nbytes += 2.0 * pages * (kv_heads * page_size * head_dim * code_bytes
                                 + scale_bytes)
        nbytes += heads * head_dim * (q_bytes + o_bytes)
    return Work(flops, nbytes)


def prefill_attention(q_offset: int, q_len: int, *, heads: int,
                      kv_heads: int, head_dim: int, page_size: int,
                      code_bytes: int = 1, scale_bytes: int = 4,
                      q_bytes: int = 4, o_bytes: int = 4) -> Work:
    """Causal attention of a slab of ``q_len`` queries at ``q_offset`` over
    the slab and the ``q_offset`` tokens before it: query i sees
    ``q_offset + i + 1`` keys."""
    visible = q_len * q_offset + q_len * (q_len + 1) / 2.0
    pages = -(-(q_offset + q_len) // page_size)
    return Work(
        4.0 * heads * head_dim * visible,
        2.0 * pages * (kv_heads * page_size * head_dim * code_bytes
                       + scale_bytes)
        + q_len * heads * head_dim * (q_bytes + o_bytes))


# ------------------------------ model FLOPs --------------------------------


def param_count(cfg: dict) -> dict:
    """Parameters of a Qwen2-style decoder from its configuration file's
    keys: ``matmul`` (every weight a token's forward multiplies by, the
    output head included) and ``total``."""
    d = cfg["hidden_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = cfg.get("head_dim") or d // h
    f, v, n = cfg["intermediate_size"], cfg["vocab_size"], \
        cfg["num_hidden_layers"]
    attn = d * h * dh + 2 * d * kv * dh + h * dh * d
    mlp = 3 * d * f
    layer_matmul = attn + mlp
    bias = h * dh + 2 * kv * dh
    norms = 2 * d
    head = d * v
    embed = d * v
    tied = bool(cfg.get("tie_word_embeddings"))
    return {
        "matmul": n * layer_matmul + head,
        "total": n * (layer_matmul + bias + norms) + d + embed
        + (0 if tied else head),
    }


def attention_flops_per_token(cfg: dict, context: float) -> float:
    """Forward FLOPs of attention's score and value products for one token
    that sees ``context`` tokens, over all layers."""
    h = cfg["num_attention_heads"]
    dh = cfg.get("head_dim") or cfg["hidden_size"] // h
    return 4.0 * cfg["num_hidden_layers"] * h * dh * context


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Model FLOPs of a training token in a sequence of ``seq`` tokens:
    6 per matmul weight plus the customary 12*L*(h*dh)*seq of attention's
    forward and backward (the causal half is not taken off).  Recomputed
    operations are not counted."""
    n = param_count(cfg)["matmul"]
    h = cfg["num_attention_heads"]
    dh = cfg.get("head_dim") or cfg["hidden_size"] // h
    return 6.0 * n + 12.0 * cfg["num_hidden_layers"] * h * dh * seq


def serve_flops_per_token(cfg: dict, context: float) -> float:
    """Model FLOPs of one served token (prefill or decode) that sees
    ``context`` tokens: 2 per matmul weight plus attention."""
    return 2.0 * param_count(cfg)["matmul"] + attention_flops_per_token(
        cfg, context)
