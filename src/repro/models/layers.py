"""Functional layer library (no flax): init fns return param pytrees,
apply fns are pure.  Compute is bf16 with f32 accumulation; params are f32.

Every dense GEMM goes through ``dense()`` which dispatches to the
reduced-precision-accumulation ``qdot`` kernel when the model's QuantPlan
assigns a config to that GEMM type — this is how the paper's technique is
integrated as a first-class feature.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.kernels.ops import QDotConfig, qdot
from repro.models.config import ModelConfig, MoEConfig, SSMConfig

Params = dict[str, Any]

COMPUTE_DTYPE = jnp.bfloat16


# --------------------------------------------------------------------------
# distribution context
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Dist:
    """How apply-fns should interact with the mesh (None = single device).

    ``shard_axis`` flips the serve-path layer fns into tensor-parallel
    mode: the code is already INSIDE a ``shard_map`` body over that mesh
    axis (so ``mesh`` stays None and ``_constrain`` is a no-op), each
    shard's params are its output-dim slices (``sharding.specs.
    serve_param_specs``), and cross-shard combines are explicit
    collectives — the psum'd attention-carry merge, tiled all_gathers
    after every output-split GEMM, and pmax-shared KV page scales.
    ``tp_size`` is the static shard count; ``logit_wire`` picks the
    unembed gather ("gather" = exact f32/bf16 movement, "int8" = the
    ``train.compression.compressed_psum`` int8 wire)."""

    mesh: Any = None
    data_axes: tuple = ("pod", "data")
    model_axis: str = "model"
    shard_axis: str | None = None
    tp_size: int = 1
    logit_wire: str = "gather"

    @property
    def ep_size(self) -> int:
        if self.mesh is None:
            return 1
        return self.mesh.shape[self.model_axis]


LOCAL = Dist()


def _constrain(x: jnp.ndarray, dist: Dist, spec: P) -> jnp.ndarray:
    if dist.mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(dist.mesh, spec)
    )


# --------------------------------------------------------------------------
# primitives
# --------------------------------------------------------------------------


def _exact_f32(a: jnp.ndarray) -> jnp.ndarray:
    """f32 carrier of a GEMM operand whose bf16 values stay exactly bf16.
    XLA may keep a fused bf16 intermediate in f32 (excess precision), and
    whether it does depends on the surrounding program; the quantizer in
    ``qdot`` would then see different inputs in, say, the single-device and
    the tensor-parallel decode step.  The explicit rounding pins them."""
    f = a.astype(jnp.float32)
    if a.dtype == jnp.bfloat16:
        f = jax.lax.reduce_precision(f, exponent_bits=8, mantissa_bits=7)
    return f


def dense(x: jnp.ndarray, w: jnp.ndarray, qcfg: QDotConfig | None = None,
          bias: jnp.ndarray | None = None,
          out_fmt=None) -> jnp.ndarray:
    """y = x @ w (+ bias); bf16 compute, f32 accumulation.

    With a QDotConfig, runs the paper's reduced-accumulation Pallas path —
    one fused pallas_call for the forward GEMM (representation quantization
    in-kernel, int8-packed QTensor residuals from the epilogue) and one for
    the whole backward (repro.kernels.bwd_pair); block decompositions come
    from the autotune tuning table (pre-fill it with
    repro.train.loop.warmup_gemm_autotune for tuned blocks).

    ``out_fmt`` is the consumer-format hint, threaded into the GEMM's
    output epilogue: pass the (1, e, m) representation format of the op
    that ingests y UNCHANGED (no nonlinearity/norm in between) and the
    rounding the consumer would apply happens inside this GEMM instead —
    the consumer can then skip its own input quantization bit-exactly
    (idempotence).  Backward treats the rounding as straight-through.
    """
    if qcfg is not None and not qcfg.is_exact:
        if out_fmt is not None and out_fmt != qcfg.out_fmt:
            qcfg = dataclasses.replace(qcfg, out_fmt=out_fmt)
        y = qdot(_exact_f32(x), _exact_f32(w), qcfg)
        y = y.astype(COMPUTE_DTYPE)
    else:
        # bf16 output dtype: on TPU the MXU still accumulates the local
        # contraction in f32 and rounds once at the end; what changes is
        # that the GSPMD cross-shard combine (the TP all-reduce of
        # row-parallel partials) runs on bf16 — HALF the wire bytes.  This
        # is exactly the paper's Corollary-1 chunked accumulation with
        # n1 = K_local (ideal intra-chunk) and n2 = TP width: the solver
        # certifies it (VRR(7, 7, 16) ~ 1, knee at n ~ 1.8e3 >> 16).
        # §Perf iteration log in EXPERIMENTS.md.
        y = jax.lax.dot_general(
            x.astype(COMPUTE_DTYPE),
            w.astype(COMPUTE_DTYPE),
            (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=COMPUTE_DTYPE,
        )
    if bias is not None:
        y = y + bias.astype(y.dtype)
    return y


def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    return out.astype(x.dtype)


def rope(q: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Rotary embedding. q: (..., S, H, d_head); positions: (..., S)."""
    d = q.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, half)
    angles = angles[..., :, None, :]  # broadcast over heads
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    q1, q2 = q[..., :half], q[..., half:]
    out = jnp.concatenate([q1 * cos - q2 * sin, q2 * cos + q1 * sin], axis=-1)
    return out.astype(q.dtype)


def _normal(key, shape, std):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(jnp.float32)


# --------------------------------------------------------------------------
# attention (GQA, optional qk-norm / qkv-bias)
# --------------------------------------------------------------------------


def attn_init(key, cfg: ModelConfig) -> Params:
    d, h, kv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    std = 1.0 / math.sqrt(d)
    p: Params = {
        "wq": _normal(ks[0], (d, h * dh), std),
        "wk": _normal(ks[1], (d, kv * dh), std),
        "wv": _normal(ks[2], (d, kv * dh), std),
        "wo": _normal(ks[3], (h * dh, d), std / math.sqrt(2 * cfg.n_layers)),
    }
    if cfg.attn_bias:
        p["bq"] = jnp.zeros((h * dh,), jnp.float32)
        p["bk"] = jnp.zeros((kv * dh,), jnp.float32)
        p["bv"] = jnp.zeros((kv * dh,), jnp.float32)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((dh,), jnp.float32)
        p["k_norm"] = jnp.ones((dh,), jnp.float32)
    return p


def _q_proj(p: Params, x: jnp.ndarray, cfg: ModelConfig, positions: jnp.ndarray):
    b, s, _ = x.shape
    dh = cfg.head_dim
    # head count from the PARAM shape, not cfg: under tensor-parallel
    # shard_map each shard holds a head slice of wq/wk/wv
    q = dense(x, p["wq"], cfg.quant.attn_qkv, p.get("bq")).reshape(b, s, -1, dh)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
    return rope(q, positions, cfg.rope_theta)


def _kv_proj(p: Params, x: jnp.ndarray, cfg: ModelConfig, positions: jnp.ndarray):
    b, s, _ = x.shape
    dh = cfg.head_dim
    k = dense(x, p["wk"], cfg.quant.attn_qkv, p.get("bk")).reshape(b, s, -1, dh)
    v = dense(x, p["wv"], cfg.quant.attn_qkv, p.get("bv")).reshape(b, s, -1, dh)
    if cfg.qk_norm:
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    k = rope(k, positions, cfg.rope_theta)
    return k, v


def _attn_layout(dist: Dist, b: int, s: int, h: int) -> str | None:
    """Layout for the O(S*T) attention inner block (§Perf iteration log).

    Without a constraint, GSPMD keeps the score/prob tensors sharded over
    'data' (batch) only — every device materializes batch_per_dev x ALL
    heads x S x T scores, which dominates the memory roofline term.

      'head' — repeat GQA K/V to the full head count and shard heads over
               'model' (Megatron layout; O lands sharded on h*dh, feeding
               row-parallel wo with no resharding)
      'seq'  — shard query positions over 'model' (odd head counts at long
               sequence; K/V stay full, causal attention needs them all)

    Measured dead ends (EXPERIMENTS.md §Perf): constraining only the score
    OUTPUT reshards the full S*T tensor (5x collective blow-up); resharding
    batch over (data x model) makes GSPMD replicate projection compute
    (2-4x FLOPs).
    """
    if dist.mesh is None:
        return None
    if s == 1:
        # decode: attention must follow the KV-cache layout (T sharded over
        # 'model' — flash-decoding split-KV); repeating/resharding the cache
        # per token costs ~cache-size wire per layer (measured regression,
        # §Perf optimized-sweep note)
        return None
    shape = dist.mesh.shape
    model = dist.model_axis if dist.model_axis in shape else None
    if model is None:
        return None
    if h % shape[model] == 0:
        return "head"
    if s % shape[model] == 0:
        return "seq"
    return None


def _gqa_attend(q, k, v, mask, cfg, dist: Dist = LOCAL) -> jnp.ndarray:
    """q: (b,s,h,dh), k/v: (b,t,kv,dh), mask: broadcastable to (b,1,1,s,t)
    or (b,1,s,t); None = full attention.  Returns (b, s, h*dh)."""
    b, s, h, dh = q.shape
    kv = cfg.n_kv_heads
    g = h // kv
    layout = _attn_layout(dist, b, s, h)
    bs = None
    if layout is not None:
        shape = dist.mesh.shape
        data_axes = tuple(a for a in dist.data_axes if a in shape)
        dt = 1
        for a in data_axes:
            dt *= shape[a]
        bs = data_axes if (data_axes and b % dt == 0) else None
        m = dist.model_axis
    if layout == "head":
        # Megatron head-parallel: replicate kv-heads g-fold, shard h
        kh = jnp.repeat(k, g, axis=2)  # (b,t,h,dh)
        vh = jnp.repeat(v, g, axis=2)
        q = _constrain(q, dist, P(bs, None, m, None))
        kh = _constrain(kh, dist, P(bs, None, m, None))
        vh = _constrain(vh, dist, P(bs, None, m, None))
        sc = jnp.einsum("bshd,bthd->bhst", q, kh,
                        preferred_element_type=jnp.float32) / math.sqrt(dh)
        if mask is not None:
            sc = jnp.where(mask if mask.ndim == 4 else mask[:, 0], sc, -jnp.inf)
        w = jax.nn.softmax(sc, axis=-1).astype(COMPUTE_DTYPE)
        o = jnp.einsum("bhst,bthd->bshd", w, vh,
                       preferred_element_type=jnp.float32)
        return o.astype(COMPUTE_DTYPE).reshape(b, s, h * dh)

    qg = q.reshape(b, s, kv, g, dh)
    if layout == "seq":
        qg = _constrain(qg, dist, P(bs, dist.model_axis, None, None, None))
    sc = jnp.einsum(
        "bskgd,btkd->bkgst", qg, k, preferred_element_type=jnp.float32
    ) / math.sqrt(dh)
    if mask is not None:
        sc = jnp.where(mask, sc, -jnp.inf)
    w = jax.nn.softmax(sc, axis=-1).astype(COMPUTE_DTYPE)
    o = jnp.einsum("bkgst,btkd->bskgd", w, v, preferred_element_type=jnp.float32)
    return o.astype(COMPUTE_DTYPE).reshape(b, s, h * dh)


def attn_apply(
    p: Params,
    x: jnp.ndarray,
    cfg: ModelConfig,
    dist: Dist,
    *,
    positions: jnp.ndarray,
    causal: bool = True,
    context: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Full (training / prefill) attention over x: (B, S, D).

    ``context`` (B, T_ctx, D) switches to cross-attention: K/V projected
    from the context, no causal mask.
    """
    if context is not None:
        ctx_pos = jnp.broadcast_to(
            jnp.arange(context.shape[1], dtype=jnp.int32)[None],
            context.shape[:2],
        )
        k, v = _kv_proj(p, context, cfg, ctx_pos)
        mask = None
    else:
        k, v = _kv_proj(p, x, cfg, positions)
        if causal:
            m = positions[:, :, None] >= positions[:, None, :]  # (B,S,S)
            mask = m[:, None, None]  # (B,1,1,S,S)
        else:
            mask = None
    q = _q_proj(p, x, cfg, positions)
    o = _gqa_attend(q, k, v, mask, cfg, dist)
    return dense(o, p["wo"], cfg.quant.attn_out)


def attn_decode(
    p: Params,
    x: jnp.ndarray,
    cache: dict[str, jnp.ndarray],
    pos: jnp.ndarray,
    cfg: ModelConfig,
    dist: Dist,
) -> tuple[jnp.ndarray, dict[str, jnp.ndarray]]:
    """One-token decode. x: (b, 1, d); cache k/v: (b, T, kv, dh); pos: ()."""
    b = x.shape[0]
    positions = jnp.full((b, 1), pos, jnp.int32)
    q = _q_proj(p, x, cfg, positions)
    k1, v1 = _kv_proj(p, x, cfg, positions)
    ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k1.astype(cache["k"].dtype), pos, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v1.astype(cache["v"].dtype), pos, axis=1)
    t = ck.shape[1]
    mask = (jnp.arange(t)[None, :] <= pos)[None, None, None]  # (1,1,1,1,T)
    o = _gqa_attend(q, ck.astype(COMPUTE_DTYPE), cv.astype(COMPUTE_DTYPE), mask, cfg, dist)
    return dense(o, p["wo"], cfg.quant.attn_out), {"k": ck, "v": cv}


def attn_cache_init(cfg: ModelConfig, batch: int, max_t: int) -> dict[str, jnp.ndarray]:
    kv, dh = cfg.n_kv_heads, cfg.head_dim
    z = jnp.zeros((batch, max_t, kv, dh), COMPUTE_DTYPE)
    return {"k": z, "v": z}


# --------------------------------------------------------------------------
# paged serving attention (repro.serve): packed QTensor KV pages + the
# online-softmax Pallas kernels with planner-chosen accumulator widths
# --------------------------------------------------------------------------


def _merge_sharded_carry(o_l, m_l, l_l, dist: Dist):
    """Gather a head-sharded attention carry to full heads, bit-exactly.

    Each shard scatters its local-head carry into a full-head buffer at
    ``axis_index * h_loc``, filling non-owned head positions with the
    merge's NEUTRAL element ``(o=0, m=NEG, l=0)``; ``psum_carry`` then
    reduces over the mesh axis.  Owners contribute ``alpha = 2^0 = 1``,
    non-owners ``alpha = 2^(NEG - m_g)`` which underflows to exactly 0 —
    the psum adds exact zeros, so the merged full-head carry is bitwise
    the concatenation of the per-shard carries (see
    ``kernels.attention.psum_carry``).  Returns finalized (..., H, dh).
    """
    from repro.kernels.attention import NEG, finalize_carry, psum_carry

    h_loc = o_l.shape[-2]
    lead = o_l.shape[:-2]
    h = h_loc * dist.tp_size
    start = jax.lax.axis_index(dist.shard_axis) * h_loc
    zero_at = (0,) * len(lead)
    o_f = jax.lax.dynamic_update_slice(
        jnp.zeros(lead + (h, o_l.shape[-1]), jnp.float32), o_l,
        zero_at + (start, 0))
    m_f = jax.lax.dynamic_update_slice(
        jnp.full(lead + (h,), NEG, jnp.float32), m_l, zero_at + (start,))
    l_f = jax.lax.dynamic_update_slice(
        jnp.zeros(lead + (h,), jnp.float32), l_l, zero_at + (start,))
    o_f, _, l_f = psum_carry(o_f, m_f, l_f, dist.shard_axis)
    return finalize_carry(o_f, l_f)


def _gather_cols(y: jnp.ndarray, dist: Dist) -> jnp.ndarray:
    """Concatenate an output-dim-split GEMM result across shards.  Pure
    data movement (no arithmetic), so the gathered result is bitwise the
    unsharded GEMM's — the dot itself is slice-invariant in N (each
    output column's contraction is untouched by the split)."""
    return jax.lax.all_gather(y, dist.shard_axis, axis=y.ndim - 1,
                              tiled=True)


def attn_decode_paged(
    p: Params,
    x: jnp.ndarray,
    kv: dict[str, jnp.ndarray],
    page_table: jnp.ndarray,
    positions: jnp.ndarray,
    seq_lens: jnp.ndarray,
    cfg: ModelConfig,
    dist: Dist,
    *,
    layer,
    kv_fmt,
    acc: tuple[int, int],
    oracle: bool = False,
) -> tuple[jnp.ndarray, dict[str, jnp.ndarray]]:
    """One-token decode of layer ``layer`` against the paged-KV arena.

    ``x`` (B, 1, D); ``kv`` = {"k", "v", "k_se", "v_se"}, the whole arena
    (``repro.serve.kvcache`` layout), which the token's K/V are scattered
    into at ``[layer, page]`` and the kernel reads at ``layer`` in place;
    ``layer`` a traced int32 scalar; ``positions`` (B,) — each sequence's
    own write position (continuous batching: they differ); ``seq_lens``
    (B,) — attended tokens incl. this one, 0 for padded rows (their writes
    land in the reserved null page).  ``acc`` is the planner's carry
    format; ``oracle=True`` swaps the Pallas kernel for the unfused jnp
    reference (the logit-exactness oracle).
    """
    from repro.kernels.attention import (
        paged_attn_decode,
        paged_attn_decode_reference,
    )
    from repro.serve import kvcache as KV

    b = x.shape[0]
    pos2 = positions[:, None]
    q = _q_proj(p, x, cfg, pos2)  # (B, 1, H, dh)
    k1, v1 = _kv_proj(p, x, cfg, pos2)
    page_size = kv["k"].shape[-2]
    page_id = jnp.take_along_axis(
        page_table, (positions // page_size)[:, None], axis=1)[:, 0]
    slot = positions % page_size
    ax = dist.shard_axis
    kk, kse = KV.append_token(kv["k"], kv["k_se"],
                              k1[:, 0].astype(jnp.float32), page_id, slot,
                              kv_fmt, pmax_axis=ax, layer=layer)
    vv, vse = KV.append_token(kv["v"], kv["v_se"],
                              v1[:, 0].astype(jnp.float32), page_id, slot,
                              kv_fmt, pmax_axis=ax, layer=layer)
    attend = paged_attn_decode_reference if oracle else paged_attn_decode
    if ax is None:
        o = attend(q[:, 0].astype(jnp.float32), kk, vv, kse, vse, page_table,
                   seq_lens, layer=layer, kv_fmt=kv_fmt, acc=acc)
    else:
        # head-sharded: each local head walks its FULL-context online
        # softmax exactly as the single-device kernel (same pages, same
        # order, same carry rounding), then the cross-shard gather is a
        # psum'd carry merge with neutral non-owner elements (exact)
        o_l, m_l, l_l = attend(q[:, 0].astype(jnp.float32), kk, vv, kse, vse,
                               page_table, seq_lens, layer=layer,
                               kv_fmt=kv_fmt, acc=acc, return_carry=True)
        o = _merge_sharded_carry(o_l, m_l, l_l, dist)
    o = o.reshape(b, 1, -1).astype(COMPUTE_DTYPE)
    new_kv = {"k": kk, "v": vv, "k_se": kse, "v_se": vse}
    y = dense(o, p["wo"], cfg.quant.attn_out)
    return (y if ax is None else _gather_cols(y, dist)), new_kv


def attn_verify_paged(
    p: Params,
    x: jnp.ndarray,
    kv: dict[str, jnp.ndarray],
    page_table: jnp.ndarray,
    positions: jnp.ndarray,
    seq_lens: jnp.ndarray,
    cfg: ModelConfig,
    dist: Dist,
    *,
    layer,
    kv_fmt,
    acc: tuple[int, int],
    oracle: bool = False,
) -> tuple[jnp.ndarray, dict[str, jnp.ndarray]]:
    """Speculative-decode verify: score ``S = k + 1`` tokens per sequence
    of layer ``layer`` against the whole paged arena (written and read in
    place at ``layer``, as ``attn_decode_paged``) in ONE batched kernel
    call, bitwise identical to ``S`` sequential ``attn_decode_paged``
    steps.

    ``x`` (B, S, D) — the last committed token followed by the k draft
    proposals; ``positions`` (B,) the FIRST write position per row;
    ``seq_lens`` (B,) the attended length at slab index 0 (``positions +
    1``; 0 for padded rows).  The K/V for all S tokens append under the
    decode path's exact per-slot discipline (slot-0 writes fix the page
    scale, later slots quantize under it — appends never read, so writing
    all S before attending changes nothing), then the (B, S) queries
    flatten to ``B * S`` independent decode rows — each with the page
    table of its sequence and its own attended length ``seq_lens + j``,
    so every row's online-softmax walk IS the decode kernel's walk at
    that context.  One compiled signature per (bucket, k) serves every
    request; verify-batch width scales the GEMM's row count, never a
    row's accumulation length (the contract ``plan_verify`` certifies).
    """
    from repro.kernels.attention import (
        paged_attn_decode,
        paged_attn_decode_reference,
    )
    from repro.serve import kvcache as KV

    b, s, _ = x.shape
    pos2 = positions[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
    q = _q_proj(p, x, cfg, pos2)  # (B, S, H, dh)
    k1, v1 = _kv_proj(p, x, cfg, pos2)
    page_size = kv["k"].shape[-2]
    ax = dist.shard_axis
    kk, kse, vv, vse = kv["k"], kv["k_se"], kv["v"], kv["v_se"]
    for j in range(s):
        pos_j = positions + j
        page_id = jnp.take_along_axis(
            page_table, (pos_j // page_size)[:, None], axis=1)[:, 0]
        slot = pos_j % page_size
        kk, kse = KV.append_token(kk, kse, k1[:, j].astype(jnp.float32),
                                  page_id, slot, kv_fmt, pmax_axis=ax,
                                  layer=layer)
        vv, vse = KV.append_token(vv, vse, v1[:, j].astype(jnp.float32),
                                  page_id, slot, kv_fmt, pmax_axis=ax,
                                  layer=layer)
    # flatten: row (i, j) attends sequence i's pages at length seq_lens+j
    # (padded rows stay 0 → the kernel emits exact zeros, nothing read)
    q_flat = q.reshape(b * s, *q.shape[2:]).astype(jnp.float32)
    pt_flat = jnp.repeat(page_table, s, axis=0)
    sl_flat = jnp.where(
        seq_lens[:, None] > 0,
        seq_lens[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :],
        0).reshape(b * s)
    attend = paged_attn_decode_reference if oracle else paged_attn_decode
    if ax is None:
        o = attend(q_flat, kk, vv, kse, vse, pt_flat, sl_flat, layer=layer,
                   kv_fmt=kv_fmt, acc=acc)
    else:
        o_l, m_l, l_l = attend(q_flat, kk, vv, kse, vse, pt_flat, sl_flat,
                               layer=layer, kv_fmt=kv_fmt, acc=acc,
                               return_carry=True)
        o = _merge_sharded_carry(o_l, m_l, l_l, dist)
    o = o.reshape(b, s, -1).astype(COMPUTE_DTYPE)
    new_kv = {"k": kk, "v": vv, "k_se": kse, "v_se": vse}
    y = dense(o, p["wo"], cfg.quant.attn_out)
    return (y if ax is None else _gather_cols(y, dist)), new_kv


def attn_prefill_bucketed(
    p: Params,
    x: jnp.ndarray,
    kv: dict[str, jnp.ndarray],
    page_row: jnp.ndarray,
    slab_page_ids: jnp.ndarray,
    q_offset,
    q_len,
    cfg: ModelConfig,
    dist: Dist,
    *,
    layer,
    kv_fmt,
    acc: tuple[int, int],
    block_q: int | None = None,
    call=None,
) -> tuple[jnp.ndarray, dict[str, jnp.ndarray]]:
    """One bucket-shaped prefill slab of ONE sequence through layer
    ``layer``: the slab's K/V are written into the paged arena and its
    queries attend history and slab in one ``flash_prefill_paged`` call.
    ``kv`` is the whole arena: the slab's pages are written at ``[layer,
    page]`` and the kernel reads the layer in place.

    ``x`` (1, T, D) is the slab padded to the bucket's fixed slab width;
    ``q_offset``/``q_len`` are TRACED int32 scalars (absolute start, live
    rows), so every slab of every prompt in the bucket — first, middle,
    ragged last, post-preemption restore — reuses one compiled instance.
    ``page_row`` (max_pages,) is the sequence's full page row padded to
    the bucket width; ``slab_page_ids`` the slab's own (padded) pages.

    The slab's K/V are quantized into ``slab_page_ids`` byte-identically
    to the unpadded walk: rows ``>= q_len`` are zeroed before the write
    (``write_prompt`` zero-fills the ragged tail internally, so the
    padded slab reproduces the exact tail-page bytes), padded page slots
    point at the reserved null page, and zero blocks encode to scale
    exponent 0 + code 0 — the null page's existing dead bytes.  Then one
    ``flash_prefill_paged`` call walks history AND slab straight off the
    post-write arena (history pages were written by earlier slabs with
    the same per-page scale grouping), per query row the same page-size
    blocks in the same order with the same carry rounding as a one-shot
    prefill — bit-identical outputs, arena and decode stream."""
    from repro.kernels.attention import flash_prefill_paged
    from repro.kernels.autotune import attn_blocks_for
    from repro.serve import kvcache as KV

    t = x.shape[1]
    page_size = kv["k"].shape[-2]
    q_offset = jnp.asarray(q_offset, jnp.int32)
    q_len = jnp.asarray(q_len, jnp.int32)
    positions = (q_offset + jnp.arange(t, dtype=jnp.int32))[None]
    q = _q_proj(p, x, cfg, positions)  # (1, T, H, dh)
    k, v = _kv_proj(p, x, cfg, positions)
    live = (jnp.arange(t, dtype=jnp.int32) < q_len)[:, None, None]
    kf = jnp.where(live, k[0].astype(jnp.float32), 0.0)
    vf = jnp.where(live, v[0].astype(jnp.float32), 0.0)
    ax = dist.shard_axis
    kk, kse = KV.write_prompt(kv["k"], kv["k_se"], kf, slab_page_ids,
                              kv_fmt, pmax_axis=ax, layer=layer)
    vv, vse = KV.write_prompt(kv["v"], kv["v_se"], vf, slab_page_ids,
                              kv_fmt, pmax_axis=ax, layer=layer)
    h_here = q.shape[2]  # local heads under tensor-parallel shard_map
    if call is None and block_q is None:
        block_q = attn_blocks_for(t, h_here, cfg.head_dim, page_size,
                                  e_acc=acc[0], m_acc=acc[1], kv_fmt=kv_fmt,
                                  max_pages=int(page_row.shape[0]))
    if call is not None and ax is not None:
        import dataclasses as _dc
        call = _dc.replace(call, h=h_here, kv_heads=kk.shape[-3])
    if ax is None:
        o = flash_prefill_paged(q[0].astype(jnp.float32), kk, vv, kse, vse,
                                page_row, q_offset, q_len, q_offset + q_len,
                                kv_fmt=kv_fmt, acc=acc, block_q=block_q or 128,
                                call=call, layer=layer)
    else:
        # same discipline as attn_decode_paged: full-context local-head
        # walk, neutral-element psum'd carry merge (exact)
        o_l, m_l, l_l = flash_prefill_paged(
            q[0].astype(jnp.float32), kk, vv, kse, vse,
            page_row, q_offset, q_len, q_offset + q_len,
            kv_fmt=kv_fmt, acc=acc, block_q=block_q or 128,
            call=call, return_carry=True, layer=layer)
        o = _merge_sharded_carry(o_l, m_l, l_l, dist)
    o = o.reshape(1, t, -1).astype(COMPUTE_DTYPE)
    new_kv = {"k": kk, "v": vv, "k_se": kse, "v_se": vse}
    y = dense(o, p["wo"], cfg.quant.attn_out)
    return (y if ax is None else _gather_cols(y, dist)), new_kv


# --------------------------------------------------------------------------
# MLP (SwiGLU)
# --------------------------------------------------------------------------


def mlp_init(key, cfg: ModelConfig, d_ff: int | None = None) -> Params:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "w_gate": _normal(ks[0], (d, f), 1.0 / math.sqrt(d)),
        "w_up": _normal(ks[1], (d, f), 1.0 / math.sqrt(d)),
        "w_down": _normal(ks[2], (f, d), 1.0 / math.sqrt(f) / math.sqrt(2 * cfg.n_layers)),
    }


def mlp_apply(p: Params, x: jnp.ndarray, cfg: ModelConfig,
              dist: Dist | None = None) -> jnp.ndarray:
    """SwiGLU.  Under a tensor-parallel ``dist.shard_axis`` every weight
    is split on its OUTPUT dim (never the contraction — an N-slice of a
    dot is bitwise the corresponding slice of the full dot, so gathered
    results equal the unsharded ones exactly; a contraction split would
    psum partial sums and round differently).  w_gate/w_up give the local
    d_ff slice, the silu gate is elementwise (exact per element), the
    hidden is all_gathered to full d_ff for w_down's contraction, and
    w_down's d_model slice is gathered back."""
    g = dense(x, p["w_gate"], cfg.quant.mlp_up)
    u = dense(x, p["w_up"], cfg.quant.mlp_up)
    h = jax.nn.silu(g) * u
    if dist is not None and dist.shard_axis is not None:
        h = _gather_cols(h, dist)
        return _gather_cols(dense(h, p["w_down"], cfg.quant.mlp_down), dist)
    return dense(h, p["w_down"], cfg.quant.mlp_down)


# --------------------------------------------------------------------------
# MoE (top-k routing, fixed capacity, expert-parallel over the model axis)
# --------------------------------------------------------------------------


def moe_init(key, cfg: ModelConfig) -> Params:
    d = cfg.d_model
    mc: MoEConfig = cfg.moe
    e, f = mc.n_experts, mc.d_ff_expert
    ks = jax.random.split(key, 5)
    p: Params = {
        "router": _normal(ks[0], (d, e), 1.0 / math.sqrt(d)),
        "w_gate": _normal(ks[1], (e, d, f), 1.0 / math.sqrt(d)),
        "w_up": _normal(ks[2], (e, d, f), 1.0 / math.sqrt(d)),
        "w_down": _normal(ks[3], (e, f, d), 1.0 / math.sqrt(f) / math.sqrt(2 * cfg.n_layers)),
    }
    if mc.n_shared:
        p["shared"] = mlp_init(ks[4], cfg, d_ff=mc.n_shared * mc.d_ff_expert)
    return p


def _moe_fused_enabled() -> bool:
    """The MoE expert MLPs route through the fused Pallas GEMM by default
    (ROADMAP "autotune coverage": the warmup pre-tunes their shapes under
    bf16-labeled table keys, and this routing is what lets those entries
    steer an actual schedule).  REPRO_MOE_FUSED=0 restores the plain XLA
    einsum path."""
    import os

    return os.environ.get("REPRO_MOE_FUSED", "1") != "0"


def _moe_expert_mlp_fused(h: jnp.ndarray, wl: jnp.ndarray, wu: jnp.ndarray,
                          wd: jnp.ndarray) -> jnp.ndarray:
    """The per-expert SwiGLU through ``qdot``'s fused kernel, one expert at
    a time (E_loc is a static small count; the loop unrolls at trace time).

    The GEMMs stay unquantized — wide accumulation, no representation
    format — so values match the einsum path up to the bf16 operand
    rounding both paths share; what changes is the executor: one
    ``pallas_call`` per GEMM whose block decomposition comes from the
    autotune table's bf16-keyed expert-shape entries (``table_dtype``),
    with ``qdot``'s custom_vjp supplying the backward.
    """
    from repro.kernels.ops import QDotConfig, qdot

    qcfg = QDotConfig(table_dtype="bf16")

    def f32(w):  # same bf16 operand rounding as the einsum path
        return w.astype(COMPUTE_DTYPE).astype(jnp.float32)

    outs = []
    for i in range(h.shape[0]):
        hi = h[i].astype(jnp.float32)
        g = qdot(hi, f32(wl[i]), qcfg).astype(COMPUTE_DTYPE)
        u = qdot(hi, f32(wu[i]), qcfg).astype(COMPUTE_DTYPE)
        a = (jax.nn.silu(g) * u).astype(jnp.float32)
        outs.append(qdot(a, f32(wd[i]), qcfg).astype(COMPUTE_DTYPE))
    return jnp.stack(outs)


def _moe_local(
    p: Params,
    x2: jnp.ndarray,  # (T, D) local tokens
    cfg: ModelConfig,
    ep_rank: jnp.ndarray | int,
    ep_size: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-device MoE: route all (replicated) tokens, compute only the
    experts owned by this model-rank, return partial output (summed across
    ranks by the caller) and the load-balance aux loss."""
    mc: MoEConfig = cfg.moe
    t, d = x2.shape
    e, k = mc.n_experts, mc.top_k
    e_loc = e // ep_size

    logits = dense(x2, p["router"]).astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, k)  # (T, k)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)

    # load-balance loss (Switch): E * sum_e fraction_tokens_e * mean_prob_e
    counts = jnp.sum(jax.nn.one_hot(idx, e, dtype=jnp.float32), axis=(0, 1))
    aux = e * jnp.sum((counts / (t * k)) * jnp.mean(probs, axis=0))

    flat_e = idx.reshape(-1)  # (T*k,)
    flat_gate = gate.reshape(-1).astype(COMPUTE_DTYPE)
    flat_tok = jnp.repeat(jnp.arange(t), k)

    lo = ep_rank * e_loc
    local = (flat_e >= lo) & (flat_e < lo + e_loc)
    le = jnp.clip(flat_e - lo, 0, e_loc - 1)

    cap = max(int(mc.capacity_factor * k * t / e), 1)
    onehot = (local[:, None] & (le[:, None] == jnp.arange(e_loc)[None, :])).astype(jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - 1  # position within expert buffer
    pos = jnp.sum(pos * onehot, axis=1)
    ok = local & (pos < cap)
    slot = jnp.where(ok, le * cap + pos, e_loc * cap)  # OOB drops

    buf = jnp.zeros((e_loc * cap + 1, d), COMPUTE_DTYPE)
    buf = buf.at[slot].set(x2.astype(COMPUTE_DTYPE)[flat_tok], mode="drop")
    h = buf[:-1].reshape(e_loc, cap, d)

    wl, wu, wd = p["w_gate"], p["w_up"], p["w_down"]  # local slices (E_loc,...)
    if _moe_fused_enabled():
        o = _moe_expert_mlp_fused(h, wl, wu, wd)
    else:
        g = jnp.einsum("ecd,edf->ecf", h, wl.astype(COMPUTE_DTYPE),
                       preferred_element_type=jnp.float32).astype(COMPUTE_DTYPE)
        u = jnp.einsum("ecd,edf->ecf", h, wu.astype(COMPUTE_DTYPE),
                       preferred_element_type=jnp.float32).astype(COMPUTE_DTYPE)
        o = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, wd.astype(COMPUTE_DTYPE),
                       preferred_element_type=jnp.float32).astype(COMPUTE_DTYPE)

    o_flat = jnp.concatenate([o.reshape(e_loc * cap, d),
                              jnp.zeros((1, d), COMPUTE_DTYPE)])
    contrib = o_flat[slot] * (flat_gate * ok.astype(COMPUTE_DTYPE))[:, None]
    y = jnp.zeros((t, d), COMPUTE_DTYPE).at[flat_tok].add(contrib)
    return y, aux


def moe_apply(
    p: Params, x: jnp.ndarray, cfg: ModelConfig, dist: Dist
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, D) -> (y, aux_loss).  Experts sharded over dist.model_axis;
    activations replicated across it (TP-style), partial outputs psum'd."""
    b, s, d = x.shape
    mc: MoEConfig = cfg.moe

    if dist.mesh is None or dist.ep_size == 1 or mc.n_experts % dist.ep_size != 0:
        y, aux = _moe_local(p, x.reshape(b * s, d), cfg, 0, 1)
        out = y.reshape(b, s, d)
    else:
        axis = dist.model_axis
        ep = dist.ep_size

        def local_fn(router, wl, wu, wd, xb):
            rank = jax.lax.axis_index(axis)
            pl = {"router": router, "w_gate": wl, "w_up": wu, "w_down": wd}
            bl, sl, dl = xb.shape
            y, aux = _moe_local(pl, xb.reshape(bl * sl, dl), cfg, rank, ep)
            y = jax.lax.psum(y, axis)
            aux = jax.lax.pmean(aux, axis)
            return y.reshape(bl, sl, dl), aux

        out, aux = shard_map(
            local_fn,
            mesh=dist.mesh,
            in_specs=(P(), P(axis), P(axis), P(axis), P(dist.data_axes)),
            out_specs=(P(dist.data_axes), P()),
            check_vma=False,
        )(p["router"], p["w_gate"], p["w_up"], p["w_down"], x)

    if mc.n_shared:
        out = out + mlp_apply(p["shared"], x, cfg)
    return out, aux.astype(jnp.float32)


# --------------------------------------------------------------------------
# Mamba-2 (SSD) block
# --------------------------------------------------------------------------


def _ssm_dims(cfg: ModelConfig):
    sc: SSMConfig = cfg.ssm
    d_inner = sc.expand * cfg.d_model
    n_heads = d_inner // sc.head_dim
    conv_ch = d_inner + 2 * sc.n_groups * sc.state_dim
    return sc, d_inner, n_heads, conv_ch


def mamba_init(key, cfg: ModelConfig) -> Params:
    sc, d_inner, nh, conv_ch = _ssm_dims(cfg)
    d = cfg.d_model
    proj_out = 2 * d_inner + 2 * sc.n_groups * sc.state_dim + nh
    ks = jax.random.split(key, 4)
    return {
        "in_proj": _normal(ks[0], (d, proj_out), 1.0 / math.sqrt(d)),
        "conv_w": _normal(ks[1], (sc.conv_kernel, conv_ch), 0.5),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, nh).astype(jnp.float32)),
        "D": jnp.ones((nh,), jnp.float32),
        "dt_bias": jnp.zeros((nh,), jnp.float32),
        "norm": jnp.ones((d_inner,), jnp.float32),
        "out_proj": _normal(ks[2], (d_inner, d), 1.0 / math.sqrt(d_inner) / math.sqrt(2 * cfg.n_layers)),
    }


def _causal_conv(u: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """Depthwise causal conv. u: (B, S, C); w: (K, C)."""
    k = w.shape[0]
    pad = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0)))
    out = sum(pad[:, i : i + u.shape[1], :] * w[i] for i in range(k))
    return jax.nn.silu(out)


def _mamba_proj(p: Params, x: jnp.ndarray, cfg: ModelConfig):
    sc, d_inner, nh, conv_ch = _ssm_dims(cfg)
    zxbcdt = dense(x, p["in_proj"])
    z, xbc, dt = jnp.split(zxbcdt, [d_inner, d_inner + conv_ch], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # (B,S,H)
    return z, xbc, dt


def _split_xbc(xbc, cfg: ModelConfig):
    sc, d_inner, nh, _ = _ssm_dims(cfg)
    gn = sc.n_groups * sc.state_dim
    xs, bs, cs = jnp.split(xbc, [d_inner, d_inner + gn], axis=-1)
    b_sh = bs.shape[:-1]
    x_ = xs.reshape(*b_sh, nh, sc.head_dim)
    b_ = bs.reshape(*b_sh, sc.n_groups, sc.state_dim)
    c_ = cs.reshape(*b_sh, sc.n_groups, sc.state_dim)
    return x_, b_, c_


def ssd_chunked(x, dt, a_neg, b_, c_, d_skip, chunk: int):
    """Chunked SSD scan (Mamba-2, arXiv:2405.21060 listing 1 semantics).

    x: (B,S,H,P), dt: (B,S,H), a_neg: (H,) negative, b_/c_: (B,S,G,N),
    d_skip: (H,).  Returns y: (B,S,H,P) and final state (B,H,N,P).
    """
    bsz, s, h, p_ = x.shape
    g = b_.shape[2]
    n = b_.shape[3]
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b_ = jnp.pad(b_, ((0, 0), (0, pad), (0, 0), (0, 0)))
        c_ = jnp.pad(c_, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nc = x.shape[1] // chunk
    hpg = h // g  # heads per group

    def to_chunks(t):
        return jnp.moveaxis(t.reshape(bsz, nc, chunk, *t.shape[2:]), 1, 0)

    xc, dtc, bc, cc = map(to_chunks, (x, dt, b_, c_))  # leading dim nc

    causal = jnp.tril(jnp.ones((chunk, chunk), bool))

    def chunk_step(state, inp):
        xk, dtk, bk, ck = inp  # (B,L,H,P), (B,L,H), (B,L,G,N) x2
        dta = dtk * a_neg  # (B,L,H), <= 0
        cum = jnp.cumsum(dta, axis=1)  # l_i
        seg_end = cum[:, -1:, :]  # l_L
        # within-chunk term: y_i += sum_{j<=i} C_i.B_j exp(l_i - l_j) dt_j x_j
        li = cum[:, :, None, :]  # (B,L,1,H)
        lj = cum[:, None, :, :]  # (B,1,L,H)
        logdecay = jnp.where(causal[None, :, :, None], li - lj, -jnp.inf)
        decay = jnp.exp(logdecay)
        cb = jnp.einsum("bign,bjgn->bijg", ck.astype(jnp.float32), bk.astype(jnp.float32))
        cb = jnp.repeat(cb, hpg, axis=-1)  # (B,L,L,H)
        w = cb * decay * dtk[:, None, :, :]  # weight of source j for query i
        y = jnp.einsum("bijh,bjhp->bihp", w.astype(COMPUTE_DTYPE), xk,
                       preferred_element_type=jnp.float32)
        # inter-chunk term: y_i += C_i . (state * exp(l_i))
        y = y + _state_out(ck, state, cum, hpg)
        # state update: h <- h * exp(l_L) + sum_j exp(l_L - l_j) dt_j B_j x_j^T
        tail = dtk * jnp.exp(seg_end - cum)  # (B,L,H)
        bh = jnp.repeat(bk.astype(jnp.float32), hpg, axis=2)  # (B,L,H,N)
        sk = jnp.einsum("bjhn,bjh,bjhp->bhnp", bh, tail, xk.astype(jnp.float32))
        state = state * jnp.exp(seg_end)[:, 0, :, None, None] + sk
        return state, y.astype(COMPUTE_DTYPE)

    init = jnp.zeros((bsz, h, n, p_), jnp.float32)
    state, ys = jax.lax.scan(chunk_step, init, (xc, dtc, bc, cc))
    y = jnp.moveaxis(ys, 0, 1).reshape(bsz, nc * chunk, h, p_)[:, :s]
    y = y + x[:, :s] * d_skip[None, None, :, None].astype(COMPUTE_DTYPE)
    return y, state


def _state_out(ck, state, cum, hpg):
    # ck: (B,L,G,N); state: (B,H,N,P); cum: (B,L,H)
    ckh = jnp.repeat(ck.astype(jnp.float32), hpg, axis=2)  # (B,L,H,N)
    return jnp.einsum("blhn,bhnp,blh->blhp", ckh, state, jnp.exp(cum))


def mamba_apply(p: Params, x: jnp.ndarray, cfg: ModelConfig, dist: Dist) -> jnp.ndarray:
    """Training / prefill path. x: (B, S, D)."""
    sc, d_inner, nh, conv_ch = _ssm_dims(cfg)
    z, xbc, dt = _mamba_proj(p, x, cfg)
    xbc = _causal_conv(xbc, p["conv_w"].astype(COMPUTE_DTYPE))
    xs, bs, cs = _split_xbc(xbc, cfg)
    a_neg = -jnp.exp(p["A_log"])
    y, _ = ssd_chunked(xs, dt, a_neg, bs, cs, p["D"], sc.chunk)
    y = y.reshape(*x.shape[:2], d_inner)
    y = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype), p["norm"], cfg.norm_eps)
    return dense(y, p["out_proj"])


def mamba_cache_init(cfg: ModelConfig, batch: int) -> dict[str, jnp.ndarray]:
    sc, d_inner, nh, conv_ch = _ssm_dims(cfg)
    return {
        "conv": jnp.zeros((batch, sc.conv_kernel - 1, conv_ch), COMPUTE_DTYPE),
        "ssm": jnp.zeros((batch, nh, sc.state_dim, sc.head_dim), jnp.float32),
    }


def mamba_decode(
    p: Params, x: jnp.ndarray, cache: dict[str, jnp.ndarray], cfg: ModelConfig, dist: Dist
) -> tuple[jnp.ndarray, dict[str, jnp.ndarray]]:
    """One-token recurrent step. x: (B, 1, D)."""
    sc, d_inner, nh, conv_ch = _ssm_dims(cfg)
    z, xbc, dt = _mamba_proj(p, x, cfg)  # (B,1,...)
    window = jnp.concatenate([cache["conv"], xbc.astype(COMPUTE_DTYPE)], axis=1)
    w = p["conv_w"].astype(COMPUTE_DTYPE)
    conv_out = jax.nn.silu(jnp.einsum("bkc,kc->bc", window, w))[:, None, :]
    new_conv = window[:, 1:, :]
    xs, bs, cs = _split_xbc(conv_out, cfg)
    a_neg = -jnp.exp(p["A_log"])
    dt1 = dt[:, 0]  # (B,H)
    decay = jnp.exp(dt1 * a_neg)  # (B,H)
    hpg = nh // sc.n_groups
    bh = jnp.repeat(bs[:, 0].astype(jnp.float32), hpg, axis=1)  # (B,H,N)
    ch = jnp.repeat(cs[:, 0].astype(jnp.float32), hpg, axis=1)
    xh = xs[:, 0].astype(jnp.float32)  # (B,H,P)
    new_ssm = cache["ssm"] * decay[..., None, None] + (
        dt1[..., None, None] * bh[..., :, None] * xh[..., None, :]
    )
    y = jnp.einsum("bhn,bhnp->bhp", ch, new_ssm) + p["D"][None, :, None] * xh
    y = y.reshape(x.shape[0], 1, d_inner).astype(COMPUTE_DTYPE)
    y = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype), p["norm"], cfg.norm_eps)
    return dense(y, p["out_proj"]), {"conv": new_conv, "ssm": new_ssm}
