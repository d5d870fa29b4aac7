"""Jit'd public wrappers around the Pallas kernels + the ``qdot`` autodiff op.

``qdot`` is how the paper's technique enters the training system: a dense
GEMM whose three back-propagation GEMMs (paper Fig. 2 — FWD, BWD, GRAD)
each run with their *own* solver-assigned accumulator format, with inputs
quantized to the representation format ((1,5,2) by default).

Pipeline shape (PR-1 fused the quantization into the GEMM; PR-2 packs the
carried values): the forward GEMM is one ``pallas_call`` that also emits its
quantized operands as **int8-packed residuals** (``repro.quant.QTensor`` —
1/4 the activation-residual HBM of the f32 carrier), and the entire backward
— both the input-gradient and the weight-gradient GEMM — is ONE more
``pallas_call`` (``repro.kernels.bwd_pair``): each landing of the incoming
gradient in VMEM is quantized once and contracted twice, and the packed
residuals are decoded in-kernel.  Two pallas passes per quantized layer per
train step, and no quantized value ever travels in an f32 carrier between
them.

When the backward-pair working set exceeds the VMEM budget (the dw carry
slab grows with N — lm_head-scale fan-outs), the backward SPLITS the pair
over N segments (``qmatmul_bwd_pair_nsplit``: dw columns per segment, the
dx chunked carry chained across segments — bit-identical to the unsplit
kernel, g still landed/quantized once per tile in total); only when even
``MAX_PAIR_SEGMENTS`` single-chunk-wide segments bust the budget does it
fall back to two fused GEMMs that re-read and re-quantize g.  Block
decompositions are consulted from the autotuner's JSON tuning table at
trace time (``blocks_for`` / ``pair_blocks_for``).

``QDotConfig.out_fmt`` is the consumer-format hint threaded down from
``models.layers.dense``: the forward epilogue rounds the output to the
consumer's representation format, closing the output-path dequant ROADMAP
item (the backward treats the rounding as straight-through, identically in
fused and oracle modes).

``QDotConfig(fused=False)`` keeps the original composition — standalone
quantize passes, f32 carriers everywhere — as a bit-exact reference oracle.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.core.policy import GEMMPrecision
from repro.kernels.autotune import (
    blocks_for,
    fmt_tuple,
    operand_dtype,
    pair_blocks_for,
    vmem_budget,
)
from repro.kernels.bwd_pair import (
    pair_segment_width,
    pair_vmem_bytes,
    qmatmul_bwd_pair,
    qmatmul_bwd_pair_nsplit,
)
from repro.kernels.common import ROUNDINGS, chunk_tile, threefry2x32
from repro.kernels.fused import qmatmul_fused
from repro.kernels.qmatmul import qmatmul_pallas
from repro.kernels.quantize import quantize_pallas
from repro.quant.formats import FPFormat
from repro.quant.qtensor import QTensor
from repro.telemetry import capture as _capture

__all__ = ["QDotConfig", "qdot", "qdot_packed", "quantize_op",
           "qdot_gemm_variants", "bwd_pair_fits", "pair_n_segments",
           "sr_role_seed"]

# Threefry key salts deriving the three GEMM roles' independent SR streams
# from one base seed.  The backward pair consumes the SAME bwd/grad seeds
# its two-fused-GEMM and N-split fallbacks would, so every backward
# realization of a qdot draws identical dither bits.
_ROLE_SALT = {"fwd": 0x9E3779B1, "bwd": 0x85EBCA77, "grad": 0xC2B2AE3D}


def sr_role_seed(seed, role: str):
    """Per-role SR seed from the base seed (uint32 Threefry mix; accepts a
    python int or a traced uint32 scalar, returns a uint32 scalar)."""
    s = jnp.asarray(seed).astype(jnp.uint32)
    out, _ = threefry2x32(s, jnp.uint32(_ROLE_SALT[role]),
                          jnp.uint32(0), jnp.uint32(1))
    return out


def _encode_seed(seed) -> jnp.ndarray:
    """uint32-valued seed -> f32 scalar (bit pattern preserved).  The seed
    rides through the custom_vjp as a float operand so per-step training
    seeds stay traced (no retrace) and the backward can hand back an
    ordinary zero cotangent."""
    return jax.lax.bitcast_convert_type(
        jnp.asarray(seed).astype(jnp.uint32), jnp.float32)


def _decode_seed(seed_f32: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.bitcast_convert_type(seed_f32, jnp.uint32)

# beyond this many N segments the split pair's x re-reads and dx carry
# round-trips stop paying for the saved g re-read; fall back to two GEMMs
MAX_PAIR_SEGMENTS = 16


def quantize_op(x: jnp.ndarray, fmt: FPFormat) -> jnp.ndarray:
    """Quantize to (1, e, m) via the standalone Pallas kernel."""
    return quantize_pallas(x, e=fmt.e, m=fmt.m)


@dataclass(frozen=True)
class QDotConfig:
    """Precision configuration for one logical dense layer.

    ``None`` for a role means ideal (wide) accumulation for that GEMM.
    ``repr_fmt=None`` disables input quantization (accumulation-only study,
    as in the paper's experiments the representations are always (1,5,2)).
    ``fused=False`` falls back to the unfused quantize->quantize->matmul
    composition (reference oracle; f32 carriers, 3+ pallas_calls per GEMM).
    ``pack_residuals`` carries the fused path's activation residuals as
    int8-packed ``QTensor`` payloads (only possible when ``repr_fmt`` fits
    in 8 bits; silently kept f32 otherwise, e.g. the (1,6,9) lm_head).
    ``out_fmt`` is the consumer-format hint: the forward output is rounded
    to this format in the GEMM epilogue (straight-through in the backward).
    ``stats_tag`` turns on in-graph telemetry (``repro.obs.ingraph``): the
    backward rule additionally collects the swamping-stats rows of all
    three roles — the pair kernel's ``collect_stats`` epilogue for BWD/GRAD
    and one stats replay of the saved residuals for FWD — and ships them
    host-side via ``io_callback`` under the tag.  The forward path and the
    dx/dw values are untouched (pinned bit-identical), and an untagged
    config traces no callback at all.  ``stats_axis`` psums each row across
    that mesh axis (``EnsembleStats.psum``) before shipping, masked to
    shard 0 so the host sees one global window.
    ``rounding`` selects the inter-chunk carry rounding for all three
    roles: ``"rne"`` (default, bit-identical to the historical kernels) or
    ``"sr"`` (stochastic rounding; fused-only).  ``sr_seed`` is the static
    base seed; each role derives its own stream via ``sr_role_seed``, and a
    per-step seed can be passed to ``qdot(..., sr_seed=)`` as a TRACED
    value (it rides through the custom_vjp as an operand, so stepping the
    seed does not retrace).
    """

    fwd: GEMMPrecision | None = None
    bwd: GEMMPrecision | None = None
    grad: GEMMPrecision | None = None
    repr_fmt: FPFormat | None = None
    fused: bool = True
    pack_residuals: bool = True
    out_fmt: FPFormat | None = None
    stats_tag: str | None = None
    stats_axis: str | None = None
    rounding: str = "rne"
    sr_seed: int = 0
    # autotune-table dtype label override for the forward consult: the MoE
    # expert einsum shapes are warmed under "bf16" keys (they are bf16 GEMMs
    # outside the quantized emulation) — routing them through qdot must look
    # those entries up rather than the default f32-carrier key
    table_dtype: str | None = None

    @property
    def is_exact(self) -> bool:
        return (
            self.fwd is None
            and self.bwd is None
            and self.grad is None
            and self.repr_fmt is None
            and self.out_fmt is None
        )

    @property
    def packs(self) -> bool:
        """Whether this config actually carries packed residuals."""
        return (self.fused and self.pack_residuals
                and self.repr_fmt is not None and self.repr_fmt.bits <= 8)


def _acc_params(p: GEMMPrecision | None) -> tuple[int, int, int]:
    """(e_acc, m_acc, chunk) for a role; chunk=0 means wide/schedule-only."""
    if p is None:
        return 8, 23, 0
    return p.e_acc, p.m_acc, p.chunk if p.chunk > 0 else 0


def _pair_chunks(cfg: QDotConfig) -> tuple[int, int]:
    """(block_t, block_n) rounding cadences of the backward pair."""
    _, _, bwd_chunk = _acc_params(cfg.bwd)
    _, _, grad_chunk = _acc_params(cfg.grad)
    bt = grad_chunk if grad_chunk > 0 else 128
    bn = bwd_chunk if bwd_chunk > 0 else 128
    return bt, bn


def pair_n_segments(cfg: QDotConfig, t: int, k: int, n: int,
                    *, vmem: int | None = None) -> int:
    """How many N segments the backward-pair kernel needs for this layer
    shape: 1 = the unsplit one-pass kernel fits the VMEM budget, s > 1 =
    the N-split pair (s pallas_calls, dx carry chained), 0 = even
    ``MAX_PAIR_SEGMENTS`` segments leave the per-segment (block_k, N_seg)
    dw carry slab over budget — fall back to two separate GEMMs.  The same
    predicate gates the trace in ``_qdot2d_bwd`` and the warmup tuner's
    work-list, so tuned entries are exactly the kernels qdot traces."""
    if not cfg.fused:
        return 0
    if vmem is None:
        vmem = vmem_budget()
    n1_t, n1_n = _pair_chunks(cfg)
    bt, bn = chunk_tile(n1_t), chunk_tile(n1_n)  # the kernel's T / N tiles
    for s in range(1, MAX_PAIR_SEGMENTS + 1):
        seg = pair_segment_width(n, s, n1_n)
        if pair_vmem_bytes(bt, 128, bn, seg, packed=cfg.packs) <= vmem:
            return s
        if seg == bn:  # already a single tile wide; no smaller segment
            break
    return 0


def bwd_pair_fits(cfg: QDotConfig, t: int, k: int, n: int,
                  *, vmem: int | None = None) -> bool:
    """Whether the UNSPLIT one-pass backward-pair kernel's working set —
    dominated by the (block_k, N_padded) dw carry slab — fits the VMEM
    budget for this layer shape (``vmem=None`` resolves the generation
    ceiling at call time)."""
    return pair_n_segments(cfg, t, k, n, vmem=vmem) == 1


def qdot_gemm_variants(cfg: QDotConfig, t: int, k: int, n: int) -> dict[str, dict]:
    """The kernel variants one ``qdot`` of x[t, k] @ w[k, n] traces, keyed
    by role, as autotuner keyword dicts (``kernel`` selects the tuner:
    "gemm" -> autotune_qmatmul, "bwd_pair" -> autotune_bwd_pair).

    This is the single source of truth the warmup autotuner keys its table
    from — the (shape, accumulator format, quantize/pack flags, residual
    emission) tuples here mirror the call sites below, so the tuned entries
    are exactly the ones ``blocks_for``/``pair_blocks_for`` look up at
    trace time.
    """
    fmt = fmt_tuple(cfg.repr_fmt)
    packs = cfg.packs
    out = {}
    for role, (m_, k_, n_, p, qa, qb, emitq) in {
        # role: (m, k, n, precision, quantize_a, quantize_b, emit_quantized)
        "fwd": (t, k, n, cfg.fwd, True, True, fmt is not None),
        "fwd_eval": (t, k, n, cfg.fwd, True, True, False),
    }.items():
        e_acc, m_acc, chunk = _acc_params(p)
        out[role] = dict(kernel="gemm", m=m_, k=k_, n=n_, chunk=chunk,
                         e_acc=e_acc, m_acc=m_acc, repr_fmt=fmt,
                         quantize_a=qa, quantize_b=qb, emit_quantized=emitq,
                         pack_residuals=packs and emitq,
                         dtype=cfg.table_dtype)
    eb, mb, cb = _acc_params(cfg.bwd)
    eg, mg, cg = _acc_params(cfg.grad)
    segs = pair_n_segments(cfg, t, k, n)
    if segs >= 1:
        # the N-split pair traces segment-width kernels; tune those shapes
        _, bn = _pair_chunks(cfg)
        n_tune = n if segs == 1 else pair_segment_width(n, segs, bn)
        out["bwd_pair"] = dict(kernel="bwd_pair", t=t, k=k, n=n_tune,
                               bwd_chunk=cb, grad_chunk=cg,
                               bwd_acc=(eb, mb), grad_acc=(eg, mg),
                               repr_fmt=fmt, packed=packs,
                               dtype=cfg.table_dtype)
    else:
        # two-call fallback: residuals consumed packed, in-kernel
        out["bwd"] = dict(kernel="gemm", m=t, k=n, n=k, chunk=cb,
                          e_acc=eb, m_acc=mb, repr_fmt=fmt,
                          quantize_a=True, quantize_b=False,
                          b_packed=packs, emit_quantized=False,
                          dtype=cfg.table_dtype)
        out["grad"] = dict(kernel="gemm", m=k, k=t, n=n, chunk=cg,
                           e_acc=eg, m_acc=mg, repr_fmt=fmt,
                           quantize_a=False, quantize_b=True,
                           a_packed=packs, emit_quantized=False,
                           dtype=cfg.table_dtype)
    return out


def _mm_fused(
    a: jnp.ndarray,
    b: jnp.ndarray,
    p: GEMMPrecision | None,
    repr_fmt: FPFormat | None,
    *,
    quantize_a: bool = True,
    quantize_b: bool = True,
    a_packed: bool = False,
    b_packed: bool = False,
    return_quantized: bool = False,
    pack_residuals: bool = False,
    out_fmt: FPFormat | None = None,
    pack_out: bool = False,
    dtype_key: str | None = None,
    rounding: str = "rne",
    sr_seed=0,
):
    """One fused pallas_call: Q(a) @ Q(b) under role-``p`` accumulation,
    block decomposition consulted from the autotune table at trace time."""
    e_acc, m_acc, chunk = _acc_params(p)
    fmt = fmt_tuple(repr_fmt)
    bm, bn, bk = blocks_for(
        a.shape[0], a.shape[1], b.shape[1], chunk,
        e_acc=e_acc, m_acc=m_acc, repr_fmt=fmt,
        emit_quantized=return_quantized,
        quantize_a=quantize_a, quantize_b=quantize_b,
        dtype=dtype_key or operand_dtype(a_packed, b_packed),
        pack_residuals=pack_residuals)
    return qmatmul_fused(
        a, b,
        repr_fmt=repr_fmt, e_acc=e_acc, m_acc=m_acc,
        block_m=bm, block_n=bn, block_k=bk,
        quantize_a=quantize_a, quantize_b=quantize_b,
        a_packed=a_packed, b_packed=b_packed,
        return_quantized=return_quantized, pack_residuals=pack_residuals,
        out_fmt=out_fmt, pack_out=pack_out,
        rounding=rounding, sr_seed=sr_seed,
    )


# ------------------------ in-graph telemetry emission -----------------------


def _chunk_of(p: GEMMPrecision | None) -> int:
    return p.chunk if (p is not None and p.chunk > 0) else 128


def _emit_stats_row(tag: str, role: str, n: int, n1: int, m_acc: int,
                    axis: str | None, raw: jnp.ndarray) -> None:
    """Ship one raw ``N_STATS`` swamping row host-side from inside the
    jitted step (``jax.experimental.io_callback``; the geometry metadata is
    trace-time static, so only the row crosses the device boundary).  With
    ``axis`` set, the row is psum'd across the mesh via
    ``EnsembleStats.psum`` and zeroed on every shard but 0 — an all-zero
    row is the raw-merge identity, so the host collector sees exactly one
    global window per emission site."""
    from jax.experimental import io_callback

    from repro.obs.ingraph import dispatch_raw
    from repro.telemetry.stats import EnsembleStats

    raw = raw.reshape(-1).astype(jnp.float32)
    if axis is not None:
        raw = EnsembleStats.from_raw(raw).psum(axis).to_raw()
        raw = jnp.where(jax.lax.axis_index(axis) == 0, raw,
                        jnp.zeros_like(raw))
    io_callback(
        functools.partial(dispatch_raw, tag, role, int(n), int(n1), int(m_acc)),
        None, raw, ordered=False)


def _emit_qdot_stats(cfg: QDotConfig, g, xp, wp, packed: bool,
                     t: int, k: int, n: int, raw_pair=None,
                     seed=None) -> None:
    """Collect + emit the three roles' stats for one tagged qdot backward.

    BWD/GRAD come from ``raw_pair`` (the one-pass pair kernel's
    ``collect_stats`` epilogue — zero extra GEMMs) when available;
    otherwise (N-split / two-GEMM fallback / oracle) they are measured by
    stats replays of the same contractions.  FWD is always a stats replay
    of the saved residuals — the forward pass itself stays untouched (its
    residual-emission epilogue is exclusive with ``collect_stats``).
    Geometry per role matches ``repro.telemetry.probe``: accumulation
    length K / N / T, chunk = the role's rounding cadence.
    """
    from repro.telemetry.stats import gemm_stats

    tag, axis = cfg.stats_tag, cfg.stats_axis
    quantize = cfg.repr_fmt is not None
    rnd = cfg.rounding
    base = seed if seed is not None else cfg.sr_seed
    role_seed = (lambda r: sr_role_seed(base, r)) if rnd == "sr" \
        else (lambda r: 0)
    if cfg.fwd is not None:
        _, st = gemm_stats(xp, wp, precision=cfg.fwd, repr_fmt=cfg.repr_fmt,
                           quantize_a=False, quantize_b=False,
                           a_packed=packed, b_packed=packed,
                           rounding=rnd, sr_seed=role_seed("fwd"))
        _emit_stats_row(tag, "fwd", k, _chunk_of(cfg.fwd), cfg.fwd.m_acc,
                        axis, st.to_raw())
    if raw_pair is not None:
        if cfg.bwd is not None:
            _emit_stats_row(tag, "bwd", n, _chunk_of(cfg.bwd), cfg.bwd.m_acc,
                            axis, raw_pair[0])
        if cfg.grad is not None:
            _emit_stats_row(tag, "grad", t, _chunk_of(cfg.grad),
                            cfg.grad.m_acc, axis, raw_pair[1])
        return
    if cfg.bwd is not None:
        _, st = gemm_stats(g, wp.T, precision=cfg.bwd, repr_fmt=cfg.repr_fmt,
                           quantize_a=quantize, quantize_b=False,
                           b_packed=packed,
                           rounding=rnd, sr_seed=role_seed("bwd"))
        _emit_stats_row(tag, "bwd", n, _chunk_of(cfg.bwd), cfg.bwd.m_acc,
                        axis, st.to_raw())
    if cfg.grad is not None:
        _, st = gemm_stats(xp.T, g, precision=cfg.grad, repr_fmt=cfg.repr_fmt,
                           quantize_a=False, quantize_b=quantize,
                           a_packed=packed,
                           rounding=rnd, sr_seed=role_seed("grad"))
        _emit_stats_row(tag, "grad", t, _chunk_of(cfg.grad), cfg.grad.m_acc,
                        axis, st.to_raw())


# ------------------------- unfused reference oracle -------------------------


def _mm(a: jnp.ndarray, b: jnp.ndarray, p: GEMMPrecision | None) -> jnp.ndarray:
    if p is None:
        return qmatmul_pallas(a, b)  # degenerate: wide accumulation
    block_k = p.chunk if p.chunk > 0 else 128
    return qmatmul_pallas(a, b, e_acc=p.e_acc, m_acc=p.m_acc, block_k=block_k)


def _maybe_q(x: jnp.ndarray, fmt: FPFormat | None) -> jnp.ndarray:
    return x if fmt is None else quantize_op(x, fmt)


# --------------------------------- qdot ------------------------------------


def qdot(x: jnp.ndarray, w: jnp.ndarray, cfg: QDotConfig, *,
         sr_seed=None) -> jnp.ndarray:
    """y[..., N] = x[..., K] @ w[K, N] with per-role reduced accumulation.

    ``sr_seed`` overrides ``cfg.sr_seed`` (only meaningful when
    ``cfg.rounding == "sr"``).  It may be a traced uint32/int scalar — the
    seed rides through the custom_vjp as an operand, so stepping it per
    training step does NOT retrace."""
    if cfg.rounding == "sr" and not cfg.fused:
        raise ValueError("rounding='sr' requires cfg.fused=True")
    lead = x.shape[:-1]
    k = x.shape[-1]
    x2 = x.reshape(-1, k)
    eff = sr_seed if sr_seed is not None else cfg.sr_seed
    if (_capture.active() and not cfg.is_exact
            and not isinstance(x2, jax.core.Tracer)
            and not isinstance(w, jax.core.Tracer)):
        # telemetry probe (repro.telemetry.probe): an EAGER forward pass
        # records each quantized GEMM's concrete operands + config so the
        # stats kernels can replay them with collect_stats=True; traced
        # (jit/grad) executions never record
        _capture.record(x=x2, w=w, cfg=cfg,
                        sr_seed=int(eff) if not isinstance(
                            eff, jax.core.Tracer) else 0)
    y2 = _qdot2d(x2, w, _encode_seed(eff), cfg)
    return y2.reshape(*lead, w.shape[1])


def qdot_packed(x: jnp.ndarray, w: jnp.ndarray, cfg: QDotConfig) -> QTensor:
    """Inference-only ``qdot`` whose output leaves the kernel as int8 codes
    of ``cfg.out_fmt`` — the serve-path / wire carrier (no f32 activation
    ever reaches HBM).  Not differentiable; training uses ``qdot``."""
    if cfg.out_fmt is None or cfg.out_fmt.bits > 8:
        raise ValueError("qdot_packed needs an out_fmt with <= 8 bits")
    if cfg.rounding == "sr" and not cfg.fused:
        raise ValueError("rounding='sr' requires cfg.fused=True")
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if not cfg.fused:
        y = _mm(_maybe_q(x2, cfg.repr_fmt), _maybe_q(w, cfg.repr_fmt), cfg.fwd)
        return QTensor.pack(y.reshape(*lead, w.shape[1]), cfg.out_fmt)
    codes = _mm_fused(x2, w, cfg.fwd, cfg.repr_fmt,
                      out_fmt=cfg.out_fmt, pack_out=True,
                      rounding=cfg.rounding,
                      sr_seed=(sr_role_seed(cfg.sr_seed, "fwd")
                               if cfg.rounding == "sr" else 0))
    return QTensor(codes.reshape(*lead, w.shape[1]), fmt=cfg.out_fmt)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _qdot2d(x: jnp.ndarray, w: jnp.ndarray, seed: jnp.ndarray,
            cfg: QDotConfig) -> jnp.ndarray:
    # ``seed`` is the SR seed bitcast to f32 (see ``_encode_seed``) so it
    # travels as an ordinary differentiable-dtype operand; ignored when
    # cfg.rounding == "rne".
    if not cfg.fused:
        y = _mm(_maybe_q(x, cfg.repr_fmt), _maybe_q(w, cfg.repr_fmt), cfg.fwd)
        return _maybe_q(y, cfg.out_fmt)
    fwd_seed = (sr_role_seed(_decode_seed(seed), "fwd")
                if cfg.rounding == "sr" else 0)
    return _mm_fused(x, w, cfg.fwd, cfg.repr_fmt, out_fmt=cfg.out_fmt,
                     dtype_key=cfg.table_dtype, rounding=cfg.rounding,
                     sr_seed=fwd_seed)


def _qdot2d_fwd(x, w, seed, cfg):
    # the seed joins the residuals ONLY in SR mode, so the RNE residual
    # pytree (and its byte count) is unchanged from the seed-less kernels
    tail = (seed,) if cfg.rounding == "sr" else ()
    if not cfg.fused:
        xq = _maybe_q(x, cfg.repr_fmt)
        wq = _maybe_q(w, cfg.repr_fmt)
        y = _maybe_q(_mm(xq, wq, cfg.fwd), cfg.out_fmt)
        return y, (xq, wq, *tail)
    fwd_seed = (sr_role_seed(_decode_seed(seed), "fwd")
                if cfg.rounding == "sr" else 0)
    if cfg.repr_fmt is None:
        # nothing to quantize: residuals are the raw operands
        return _mm_fused(x, w, cfg.fwd, None, out_fmt=cfg.out_fmt,
                         dtype_key=cfg.table_dtype, rounding=cfg.rounding,
                         sr_seed=fwd_seed), (x, w, *tail)
    # one pallas_call: FWD GEMM + residual emission from the epilogue —
    # int8-packed QTensor payloads when the format fits in 8 bits
    packs = cfg.packs
    y, xq, wq = _mm_fused(x, w, cfg.fwd, cfg.repr_fmt,
                          return_quantized=True, pack_residuals=packs,
                          out_fmt=cfg.out_fmt, rounding=cfg.rounding,
                          sr_seed=fwd_seed)
    if packs:
        return y, (QTensor(xq, fmt=cfg.repr_fmt),
                   QTensor(wq, fmt=cfg.repr_fmt), *tail)
    return y, (xq, wq, *tail)


def _qdot2d_bwd(cfg, res, g):
    if cfg.rounding == "sr":
        xq, wq, seed = res
    else:
        (xq, wq), seed = res, None
    dseed = jnp.zeros((), jnp.float32)  # seed gets a zero cotangent
    tagged = cfg.stats_tag is not None
    if not cfg.fused:
        gq = _maybe_q(g, cfg.repr_fmt)
        dx = _mm(gq, wq.T, cfg.bwd)
        dw = _mm(xq.T, gq, cfg.grad)
        if tagged:
            _emit_qdot_stats(cfg, g, xq, wq, False,
                             xq.shape[0], xq.shape[1], wq.shape[1])
        return dx.astype(wq.dtype), dw.astype(wq.dtype), dseed
    # out_fmt's epilogue rounding is straight-through: g passes unscaled
    # (identically in the oracle above, so fused == oracle bit-for-bit)
    packed = isinstance(xq, QTensor)
    xp = xq.payload if packed else xq
    wp = wq.payload if packed else wq
    t, k = xp.shape
    n = wp.shape[1]
    eb, mb, cb = _acc_params(cfg.bwd)
    eg, mg, cg = _acc_params(cfg.grad)
    segs = pair_n_segments(cfg, t, k, n)
    if segs >= 1:
        # the whole backward in ONE pallas_call (or, for wide-N layers whose
        # dw carry slab busts VMEM, ``segs`` segment calls with the dx carry
        # chained — bit-identical, still one g landing per tile in total):
        # g is quantized once per landing, residuals are unpacked in-kernel
        seg_n = n if segs == 1 else pair_segment_width(
            n, segs, _pair_chunks(cfg)[1])
        bt, bk, bn = pair_blocks_for(
            t, k, seg_n, bwd_chunk=cb, grad_chunk=cg, bwd_acc=(eb, mb),
            grad_acc=(eg, mg), repr_fmt=fmt_tuple(cfg.repr_fmt),
            packed=packed, dtype=cfg.table_dtype or "f32")
        s = _decode_seed(seed) if cfg.rounding == "sr" else 0
        sb = sr_role_seed(s, "bwd") if cfg.rounding == "sr" else 0
        sg = sr_role_seed(s, "grad") if cfg.rounding == "sr" else 0
        kw = dict(repr_fmt=cfg.repr_fmt, bwd_acc=(eb, mb),
                  grad_acc=(eg, mg), block_t=bt, block_k=bk, block_n=bn,
                  packed=packed, quantize_g=cfg.repr_fmt is not None,
                  rounding=cfg.rounding, sr_seed_bwd=sb, sr_seed_grad=sg)
        if segs == 1:
            if tagged:
                # same blocks, collect_stats epilogue on: dx/dw stay
                # bit-identical (shadow carries are extra outputs, not a
                # different reduction), BWD+GRAD stats come for free
                dx, dw, raw = qmatmul_bwd_pair(g, xp, wp,
                                               collect_stats=True, **kw)
                _emit_qdot_stats(cfg, g, xp, wp, packed, t, k, n,
                                 raw_pair=raw, seed=s)
            else:
                dx, dw = qmatmul_bwd_pair(g, xp, wp, **kw)
        else:
            dx, dw = qmatmul_bwd_pair_nsplit(g, xp, wp, n_split=segs, **kw)
            if tagged:
                _emit_qdot_stats(cfg, g, xp, wp, packed, t, k, n, seed=s)
        return dx, dw, dseed
    # VMEM fallback: two fused GEMMs, residuals still consumed packed
    # (the int8 transpose is an XLA copy, not a pallas pass)
    s = _decode_seed(seed) if cfg.rounding == "sr" else 0
    sb = sr_role_seed(s, "bwd") if cfg.rounding == "sr" else 0
    sg = sr_role_seed(s, "grad") if cfg.rounding == "sr" else 0
    # BWD GEMM: dx[T, K] = g[T, N] @ w^T[N, K]   (accumulation length N)
    dx = _mm_fused(g, wp.T, cfg.bwd, cfg.repr_fmt,
                   quantize_a=True, quantize_b=False, b_packed=packed,
                   dtype_key=cfg.table_dtype,
                   rounding=cfg.rounding, sr_seed=sb)
    # GRAD GEMM: dw[K, N] = x^T[K, T] @ g[T, N]  (accumulation length T —
    # the long one, B*T tokens; the paper's critical case)
    dw = _mm_fused(xp.T, g, cfg.grad, cfg.repr_fmt,
                   quantize_a=False, quantize_b=True, a_packed=packed,
                   dtype_key=cfg.table_dtype,
                   rounding=cfg.rounding, sr_seed=sg)
    if tagged:
        _emit_qdot_stats(cfg, g, xp, wp, packed, t, k, n, seed=s)
    return dx, dw, dseed


_qdot2d.defvjp(_qdot2d_fwd, _qdot2d_bwd)
