"""Pallas TPU kernel: fused representation-quantization + chunked GEMM.

The paper's MAC unit is ONE datapath: (1,5,2)-quantized operands feed a
multiplier whose running sum lives in a narrow (1, e_acc, m_acc) chunked
accumulator.  The unfused software realization (quantize_pallas on A, on B,
then qmatmul_pallas) pays two extra HBM round-trips per GEMM just to
materialize the quantized operands.  This kernel moves the representation
quantization of each A/B tile *inside* the matmul body — operands are
quantized on the VPU right after the tile lands in VMEM, then contracted on
the MXU — so one ``pallas_call`` covers the whole datapath.

Bit-exactness contract: ``quantize_block`` is elementwise and zero-padding
is a fixed point of the quantizer, so quantizing per-tile inside the kernel
produces exactly the values the standalone pre-pass would have written to
HBM; the chunked-carry rounding then sees identical inputs in an identical
order.  ``tests/test_fused.py`` pins this (assert_array_equal against the
unfused composition, ragged shapes included).

The tile quantization is recomputed per grid step (an A-tile is re-quantized
once per N-tile visit).  That is VPU work overlapped with the MXU contraction
and is the standard fusion trade: redundant on-chip compute for eliminated
HBM traffic.

Epilogues and carriers (this file is where every quantized value changes
representation, so all three conversions live in the kernel body, never as a
standalone elementwise pass):

* ``return_quantized=True`` emits the quantized operands as residuals —
  with ``pack_residuals=True`` as int8 ``(1, e_r, m_r)`` codes
  (``repro.quant.qtensor`` layout), 1/4 the HBM of the f32 carrier.  Each
  residual block is written on its FIRST grid visit only (``pl.when`` on the
  orthogonal grid axis), so emission costs one HBM write per block, not one
  per revisit, and the pallas-pass count is a faithful HBM-traffic proxy.
  (Caveat for compiled TPU: predicated-out revisits rely on Mosaic eliding
  the copy-back of untouched output windows; re-validate on silicon together
  with the interpret-mode timing proxy — see the ROADMAP TPU item.)
* ``a_packed`` / ``b_packed`` accept int8-packed operands and unpack them in
  VMEM right after the tile DMA — the backward GEMMs consume the packed
  residuals with no standalone decode pass.
* ``out_fmt`` folds the CONSUMER's representation quantization into the
  output epilogue: the emitted tile is already ``(1, e_out, m_out)``, so the
  next kernel can skip its in-kernel operand quantization (idempotence makes
  skipping bit-exact) and no separate output-path dequant/requant pass
  exists.  ``pack_out=True`` additionally emits the output itself as int8
  codes for transport/storage consumers (serve-path activations, the wire).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.autotune import fmt_tuple, register_kernel
from repro.kernels.common import (
    N_STATS,
    ROUNDINGS,
    chunk_tile,
    fold_chunks,
    pad2d,
    quantize_block,
    resolve_interpret,
    stats_delta_row,
    stats_update,
)
from repro.quant.qtensor import pack_block, unpack_block

__all__ = ["qmatmul_fused", "as_sr_seed"]


def as_sr_seed(seed) -> jnp.ndarray:
    """Normalize a python int / scalar uint32 seed to the (1, 1) uint32
    operand the SR kernels take (traced, so per-step seeds don't retrace)."""
    arr = jnp.asarray(seed)
    if arr.dtype != jnp.uint32:
        arr = arr.astype(jnp.uint32)
    return arr.reshape(1, 1)

# identity quantization (folds away inside quantize_block at trace time)
_WIDE = (8, 23)


def _load_operand(ref, *, packed: bool, q: bool, e_r: int, m_r: int):
    """One operand tile, as quantized f32 values in VMEM: unpack int8 codes,
    or quantize the f32 carrier in-kernel (both VPU work overlapped with the
    MXU contraction)."""
    if packed:
        return unpack_block(ref[...], e_r, m_r)
    x = ref[...]
    return quantize_block(x, e_r, m_r) if q else x


def _emit_output(o_ref, acc, *, e_o: int, m_o: int, pack_out: bool):
    """Output epilogue: fold the consumer's representation quantization (and
    optionally the int8 packing) into the same kernel."""
    out = acc
    if (e_o, m_o) != _WIDE:
        out = quantize_block(out, e_o, m_o)
    if pack_out:
        out = pack_block(out, e_o, m_o)
    o_ref[...] = out


def _fused_kernel(*refs, e_r, m_r, qa, qb, e_acc, m_acc, a_packed, b_packed,
                  e_o, m_o, pack_out, rounding, n, n1):
    if rounding == "sr":
        a_ref, b_ref, seed_ref, o_ref, acc_ref = refs
    else:
        a_ref, b_ref, o_ref, acc_ref = refs
        seed_ref = None

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # representation quantization / unpacking of the operand tiles (VPU)
    a = _load_operand(a_ref, packed=a_packed, q=qa, e_r=e_r, m_r=m_r)
    b = _load_operand(b_ref, packed=b_packed, q=qb, e_r=e_r, m_r=m_r)
    # intra-chunk: MXU contractions in ideal (f32) accumulation; inter-chunk:
    # carry update rounded to the (1, e_acc, m_acc) format
    bm, bn = acc_ref.shape
    acc_ref[...] = fold_chunks(
        acc_ref[...], a, b, n1=n1, tile_idx=pl.program_id(2),
        rounding=rounding, seed_ref=seed_ref, e_acc=e_acc, m_acc=m_acc,
        row0=pl.program_id(0) * bm, col0=pl.program_id(1) * bn, n_cols=n)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _emit():
        _emit_output(o_ref, acc_ref[...], e_o=e_o, m_o=m_o, pack_out=pack_out)


def _fused_kernel_emitq(*refs, e_r, m_r, qa, qb, e_acc, m_acc, packr,
                        e_o, m_o, pack_out, rounding, n, n1):
    if rounding == "sr":
        a_ref, b_ref, seed_ref, o_ref, aq_ref, bq_ref, acc_ref = refs
    else:
        a_ref, b_ref, o_ref, aq_ref, bq_ref, acc_ref = refs
        seed_ref = None

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = quantize_block(a_ref[...], e_r, m_r) if qa else a_ref[...]
    b = quantize_block(b_ref[...], e_r, m_r) if qb else b_ref[...]

    # residual emission on the FIRST visit only: the aq block ignores the j
    # grid axis (bq ignores i), so without the predicate every revisit
    # rewrites the same deterministic values — pure write amplification
    @pl.when(pl.program_id(1) == 0)
    def _store_a():
        aq_ref[...] = pack_block(a, e_r, m_r) if packr else a

    @pl.when(pl.program_id(0) == 0)
    def _store_b():
        bq_ref[...] = pack_block(b, e_r, m_r) if packr else b

    bm, bn = acc_ref.shape
    acc_ref[...] = fold_chunks(
        acc_ref[...], a, b, n1=n1, tile_idx=pl.program_id(2),
        rounding=rounding, seed_ref=seed_ref, e_acc=e_acc, m_acc=m_acc,
        row0=pl.program_id(0) * bm, col0=pl.program_id(1) * bn, n_cols=n)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _emit():
        _emit_output(o_ref, acc_ref[...], e_o=e_o, m_o=m_o, pack_out=pack_out)


def _fused_kernel_stats(*refs, e_r, m_r, qa, qb, e_acc, m_acc,
                        a_packed, b_packed, e_o, m_o, pack_out,
                        m, n, block_m, block_n, rounding, n1):
    """The swamping-telemetry variant (``collect_stats=True``): the SAME
    chunked accumulation — identical values, identical order — plus a wide
    (f32) shadow carry and an (1, N_STATS) stats reduction (see
    ``repro.kernels.common``).  The measured-VRR numerator/denominator are
    the reduced-precision and ideal accumulations of the *same* quantized
    products, so the ratio isolates the accumulation effect exactly as the
    paper's VRR does.  Stats live in a scratch row reduced across the whole
    grid; the stats output block maps every grid step to block (0, 0) and is
    written once, on the final step (same single-write discipline — and the
    same compiled-TPU copy-back caveat — as the residual emission)."""
    if rounding == "sr":
        a_ref, b_ref, seed_ref, o_ref, stats_ref, acc_ref, ideal_ref, \
            stats_acc = refs
    else:
        a_ref, b_ref, o_ref, stats_ref, acc_ref, ideal_ref, stats_acc = refs
        seed_ref = None
    i, j, kk = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    last_k = kk == pl.num_programs(2) - 1

    @pl.when((i == 0) & (j == 0) & (kk == 0))
    def _init_stats():
        stats_acc[...] = jnp.zeros_like(stats_acc)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        ideal_ref[...] = jnp.zeros_like(ideal_ref)

    a = _load_operand(a_ref, packed=a_packed, q=qa, e_r=e_r, m_r=m_r)
    b = _load_operand(b_ref, packed=b_packed, q=qb, e_r=e_r, m_r=m_r)
    # valid-region mask: zero-padding is a fixed point of the whole pipeline
    # (the padded outputs are exact), but including them in the ensemble
    # would bias the variance estimate toward zero
    rows = i * block_m + jax.lax.broadcasted_iota(
        jnp.int32, (block_m, block_n), 0)
    cols = j * block_n + jax.lax.broadcasted_iota(
        jnp.int32, (block_m, block_n), 1)
    mask = (rows < m) & (cols < n)
    ideal = [ideal_ref[...]]

    def on_chunk(prev, new, partial, last):
        ideal[0] = ideal[0] + partial
        delta, step_max = stats_delta_row(new, prev, ideal[0], partial, mask,
                                          last_k & last)
        stats_update(stats_acc, delta[None, :], step_max[None])

    acc_ref[...] = fold_chunks(
        acc_ref[...], a, b, n1=n1, tile_idx=kk, rounding=rounding,
        seed_ref=seed_ref, e_acc=e_acc, m_acc=m_acc, row0=i * block_m,
        col0=j * block_n, n_cols=n, on_chunk=on_chunk)
    ideal_ref[...] = ideal[0]

    @pl.when(last_k)
    def _emit():
        _emit_output(o_ref, acc_ref[...], e_o=e_o, m_o=m_o, pack_out=pack_out)

    @pl.when((i == pl.num_programs(0) - 1) & (j == pl.num_programs(1) - 1)
             & last_k)
    def _emit_stats():
        stats_ref[...] = stats_acc[...]


@functools.partial(
    jax.jit,
    static_argnames=("e_r", "m_r", "e_acc", "m_acc", "block_m", "block_n",
                     "n1", "qa", "qb", "emitq", "packr", "a_packed",
                     "b_packed", "e_o", "m_o", "pack_out", "collect_stats",
                     "rounding", "interpret"),
)
def _qmatmul_fused(a, b, sr_seed, *, e_r, m_r, e_acc, m_acc, block_m,
                   block_n, n1, qa, qb, emitq, packr, a_packed,
                   b_packed, e_o, m_o, pack_out, collect_stats=False,
                   rounding="rne", interpret=False):
    m, k = a.shape
    _, n = b.shape
    block_k = chunk_tile(n1)
    a32 = pad2d(a, block_m, block_k, dtype=jnp.int8 if a_packed else jnp.float32)
    b32 = pad2d(b, block_k, block_n, dtype=jnp.int8 if b_packed else jnp.float32)
    mp, kp = a32.shape
    np_ = b32.shape[1]
    grid = (mp // block_m, np_ // block_n, kp // block_k)

    kw = dict(e_r=e_r, m_r=m_r, qa=qa, qb=qb, e_acc=e_acc, m_acc=m_acc,
              e_o=e_o, m_o=m_o, pack_out=pack_out, rounding=rounding, n=n,
              n1=n1)
    in_specs = [
        pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
    ]
    # the SR seed rides in as a (1, 1) uint32 operand (traced, so a per-step
    # training seed does not retrace), broadcast to every grid step
    operands = (a32, b32)
    if rounding == "sr":
        in_specs = in_specs + [pl.BlockSpec((1, 1), lambda i, j, kk: (0, 0))]
        operands = (a32, b32, sr_seed)
    o_spec = pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j))
    o_shape = jax.ShapeDtypeStruct((mp, np_),
                                   jnp.int8 if pack_out else jnp.float32)
    # f32 VMEM carry tile: storage of the emulated narrow accumulator (its
    # value is always exactly representable in (1, e_acc, m_acc) after the
    # per-chunk rounding)
    scratch = [pltpu.VMEM((block_m, block_n), jnp.float32)]

    if collect_stats:
        out, stats = pl.pallas_call(
            functools.partial(_fused_kernel_stats, a_packed=a_packed,
                              b_packed=b_packed, m=m,
                              block_m=block_m, block_n=block_n, **kw),
            grid=grid,
            in_specs=in_specs,
            out_specs=[
                o_spec,
                pl.BlockSpec((1, N_STATS), lambda i, j, kk: (0, 0)),
            ],
            out_shape=[
                o_shape,
                jax.ShapeDtypeStruct((1, N_STATS), jnp.float32),
            ],
            scratch_shapes=scratch + [
                pltpu.VMEM((block_m, block_n), jnp.float32),  # ideal carry
                pltpu.VMEM((1, N_STATS), jnp.float32),        # stats row
            ],
            interpret=interpret,
        )(*operands)
        return out[:m, :n], stats[0]

    if not emitq:
        out = pl.pallas_call(
            functools.partial(_fused_kernel, a_packed=a_packed,
                              b_packed=b_packed, **kw),
            grid=grid,
            in_specs=in_specs,
            out_specs=o_spec,
            out_shape=o_shape,
            scratch_shapes=scratch,
            interpret=interpret,
        )(*operands)
        return out[:m, :n]

    rdt = jnp.int8 if packr else jnp.float32
    out, aq, bq = pl.pallas_call(
        functools.partial(_fused_kernel_emitq, packr=packr, **kw),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            o_spec,
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
        ],
        out_shape=[
            o_shape,
            jax.ShapeDtypeStruct((mp, kp), rdt),
            jax.ShapeDtypeStruct((kp, np_), rdt),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
    )(*operands)
    return out[:m, :n], aq[:m, :k], bq[:k, :n]


@register_kernel("qmatmul_fused")
def qmatmul_fused(
    a: jnp.ndarray,
    b: jnp.ndarray,
    *,
    repr_fmt=None,
    e_acc: int = 8,
    m_acc: int = 23,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    quantize_a: bool = True,
    quantize_b: bool = True,
    return_quantized: bool = False,
    pack_residuals: bool = False,
    a_packed: bool = False,
    b_packed: bool = False,
    out_fmt=None,
    pack_out: bool = False,
    collect_stats: bool = False,
    rounding: str = "rne",
    sr_seed=0,
    interpret: bool | None = None,
):
    """C[M, N] = Q(A)[M, K] @ Q(B)[K, N] with chunked (1, e_acc, m_acc)
    accumulation, quantization fused into the GEMM (one ``pallas_call``).

    * ``repr_fmt`` — representation format for the in-kernel operand
      quantization: an ``FPFormat``, an ``(e, m)`` tuple, or None for no
      quantization (then this is exactly ``qmatmul_pallas``).
    * ``quantize_a`` / ``quantize_b`` — per-operand opt-out, used by the
      backward pass where residuals are already stored quantized.
    * ``a_packed`` / ``b_packed`` — the operand arrives as int8 ``(1, e_r,
      m_r)`` codes (a ``QTensor`` payload) and is unpacked in VMEM; implies
      the operand needs no quantization.
    * ``block_k`` is the chunk length n1 (numerics: the carry is rounded
      once per n1 products).  It must divide 128 or be a multiple of it:
      the kernel loads K tiles of ``chunk_tile(n1)`` elements and folds
      each tile's chunks in order.  ``block_m``/``block_n`` are
      schedule-only (any choice is bit-identical — the per-output-element
      reduction order over K is fixed).
    * ``interpret=None`` interprets on the CPU backend only.
    * ``return_quantized=True`` returns ``(c, q_a, q_b)``: the quantized
      operands are emitted from the same kernel for residual saving, as int8
      codes when ``pack_residuals=True`` (each block written on its first
      grid visit only).
    * ``out_fmt`` — consumer-format hint: the output tile is quantized to
      this (1, e, m) format in the epilogue, so a downstream kernel that
      would quantize this tensor to the same format can skip it (bit-exact
      by idempotence).  ``pack_out=True`` emits the output as int8 codes.
    * ``collect_stats=True`` returns ``(c, stats)``: the swamping-telemetry
      epilogue reduces the raw (N_STATS,) stats vector (see
      ``repro.kernels.common``) alongside the GEMM — ``c`` itself is
      bit-identical to the stats-off call.  Interpret with
      ``repro.telemetry.stats.EnsembleStats.from_raw``.  Mutually exclusive
      with ``return_quantized`` (the telemetry probe path never needs
      residuals).
    * ``rounding`` — inter-chunk carry rounding: ``"rne"`` (default,
      bit-identical to the historical kernels — no extra operand, no code
      path change) or ``"sr"`` (stochastic rounding driven by an in-kernel
      Threefry counter PRNG).  ``sr_seed`` may be a python int or a traced
      uint32 scalar; given a seed, SR outputs are bitwise-reproducible, and
      identical across the fused / bwd-pair / stats-epilogue variants.
    """
    if a.ndim != 2 or b.ndim != 2 or a.shape[1] != b.shape[0]:
        raise ValueError(f"bad shapes {a.shape} @ {b.shape}")
    e_r, m_r = fmt_tuple(repr_fmt) or _WIDE
    if (a_packed or b_packed) and fmt_tuple(repr_fmt) is None:
        raise ValueError("packed operands need repr_fmt to decode")
    if a_packed and a.dtype != jnp.int8:
        raise ValueError(f"a_packed expects int8 codes, got {a.dtype}")
    if b_packed and b.dtype != jnp.int8:
        raise ValueError(f"b_packed expects int8 codes, got {b.dtype}")
    if (a_packed or b_packed) and return_quantized:
        raise ValueError("residual emission is a forward-only epilogue; "
                         "packed operands are a backward-only input")
    e_o, m_o = fmt_tuple(out_fmt) or _WIDE
    if pack_out and fmt_tuple(out_fmt) is None:
        raise ValueError("pack_out needs out_fmt to define the code layout")
    if collect_stats and return_quantized:
        raise ValueError("collect_stats is a probe-path epilogue; residual "
                         "emission is a train-path epilogue — pick one")
    if rounding not in ROUNDINGS:
        raise ValueError(f"rounding must be one of {ROUNDINGS}, "
                         f"got {rounding!r}")
    return _qmatmul_fused(
        a, b, as_sr_seed(sr_seed),
        e_r=int(e_r), m_r=int(m_r), e_acc=e_acc, m_acc=m_acc,
        block_m=block_m, block_n=block_n, n1=int(block_k),
        qa=quantize_a and not a_packed, qb=quantize_b and not b_packed,
        emitq=return_quantized, packr=pack_residuals,
        a_packed=a_packed, b_packed=b_packed,
        e_o=int(e_o), m_o=int(m_o), pack_out=pack_out,
        collect_stats=collect_stats, rounding=rounding,
        interpret=resolve_interpret(interpret),
    )
