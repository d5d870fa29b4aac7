"""Pallas TPU kernels: serve-path flash attention over the paged KV-cache.

Attention over a growing KV-cache is the longest accumulation in the serving
system — the softmax-weighted value sum reduces over every cached token — so
it is where the paper's variance-retention analysis pays the largest
inference dividend.  Two kernels cover the serve path:

* ``flash_prefill`` — causal online-softmax attention over a prompt
  (one sequence), KV visited in ``chunk``-length blocks.  It is
  **resumable**: ``carry=(o, m, l)`` feeds a previous call's online-softmax
  state back in and ``return_carry=True`` hands the raw state out instead of
  the finalized output, while ``q_offset``/``kv_offset`` place the query and
  KV slabs on the absolute token axis.  Because the running max lives on the
  integer base-2 lattice and the o/l carries are already rounded to the
  accumulator format after every block, the carry round-trips through HBM
  exactly — splitting the KV walk at any block boundary and resuming is
  bit-identical to the one-shot walk.  Chunked prefill
  (``repro.serve.scheduler``) leans on this: each ``prefill_chunk_tokens``
  query slab attends its page-aligned KV history with a carry-out call and
  folds its own causal slab with a carry-in call.
* ``paged_attn_decode`` — single-token decode against the paged QTensor
  KV-cache (``repro.serve.kvcache``): the page table and per-page scale
  exponents ride in as scalar-prefetch operands, and each grid step walks
  a block of up to 256 tokens of one sequence (``_decode_block_pages``
  pages) for all its KV heads.  The block's int8 pages are DMA'd into a
  double-buffered VMEM scratch while the previous block computes (heads
  narrower than the 128 lanes take them as pipelined grid operands;
  blocks past the sequence's length fetch nothing), unpacked in VMEM
  (``repro.quant.qtensor`` layout, times each page's power-of-two scale)
  and scored in one contraction; the online softmax then folds the block
  page by page, its carry rounded once per page as before — no
  dequantized copy of the cache ever exists in HBM.
* ``flash_prefill_paged`` — causal prefill rebuilt on the decode kernel's
  scalar-prefetch pattern: the page row, per-page scale exponents and the
  absolute-axis geometry (``q_offset``/``q_len``/``kv_len``/``start_page``)
  are all TRACED operands, the page row is padded to the bucket width and
  ``pl.when`` masks past the live page count — so ONE compiled kernel per
  attention bucket (``repro.serve.plan``) serves every slab of every prompt
  in the bucket, aligned or ragged, history and fresh slab walked in a
  single pass over the post-write arena.  Bit-identical to the dense
  ``flash_prefill`` walk at the same ``chunk == page_size`` cadence.

Accumulation discipline (the same chunked low-precision carry as
``fused.py``): within one KV block the score and weighted-value contractions
run in ideal f32 (intra-chunk); across blocks the THREE online-softmax
carries — the output accumulator ``o`` and the denominator ``l`` — are
rounded to the planner's ``(1, e_acc, m_acc)`` accumulator format after
every block update (``repro.serve.plan`` sizes the format per context-length
bucket with the paper's §4.4 knee test; the running max ``m`` is exact — it
is order statistics, not an accumulation).  The per-block update, shared
verbatim by the kernels and the unfused references, is ``_online_update``.

Bit-exactness contract: ``*_reference`` are unfused jnp oracles that walk
the same blocks in the same order with the same carry rounding —
``tests/test_serve.py`` pins kernel == reference exactly (ragged page
tails, decode at page boundaries, packed-vs-f32 KV parity included).

``paged_attn_decode(collect_stats=True)`` is the serve-time telemetry
variant: alongside the quantized carries it runs a wide (f32) shadow ``o``
accumulation and reduces the raw ``N_STATS`` swamping vector
(``repro.kernels.common`` layout, ``repro.telemetry.stats.EnsembleStats``
consumes it) so a context that outgrows its planned accumulator width is
measurable live; the attention output is bit-identical to the stats-off
call.
"""

from __future__ import annotations

import functools
import math
from contextlib import contextmanager

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.autotune import AttnCall, fmt_tuple, register_kernel
from repro.kernels.common import (
    LANES,
    N_STATS,
    ROUNDINGS,
    quantize_block,
    quantize_block_sr,
    resolve_interpret,
    sr_random_bits,
    stats_delta_row,
    stats_update,
)
from repro.quant.qtensor import unpack_block

__all__ = [
    "flash_prefill",
    "flash_prefill_reference",
    "flash_prefill_paged",
    "flash_prefill_paged_reference",
    "paged_attn_decode",
    "paged_attn_decode_reference",
    "psum_carry",
    "merge_carries",
    "finalize_carry",
    "kernel_trace_counts",
    "reset_kernel_trace_counts",
    "counting_traces",
    "NEG",
]

# Trace instrumentation: the python body of each jitted kernel wrapper runs
# exactly once per trace (shape-driven retraces included), so bumping a
# counter there counts compilations — the compile-count regression tests
# pin one trace per (bucket, kernel) across arbitrary slab geometries.
_TRACE_COUNTS: dict[str, int] = {}


def kernel_trace_counts() -> dict[str, int]:
    """Traces per kernel since the last reset (process-wide)."""
    return dict(_TRACE_COUNTS)


def reset_kernel_trace_counts() -> None:
    _TRACE_COUNTS.clear()


@contextmanager
def counting_traces():
    """Snapshot-delta view of the trace counters: yields a dict filled with
    the with-block's DELTA on exit, without mutating the process-wide
    counters.  Compile-count regression tests assert on the scoped delta
    instead of calling ``reset_kernel_trace_counts()``, so they cannot race
    each other's resets under any pytest ordering."""
    before = dict(_TRACE_COUNTS)
    delta: dict[str, int] = {}
    try:
        yield delta
    finally:
        for name, count in _TRACE_COUNTS.items():
            d = count - before.get(name, 0)
            if d:
                delta[name] = d


def _count_trace(name: str) -> None:
    _TRACE_COUNTS[name] = _TRACE_COUNTS.get(name, 0) + 1

# Mask value for invalid scores.  A large finite negative instead of -inf:
# exp2(NEG - m) underflows to exactly 0.0 in f32 for any finite running max
# m, and finite arithmetic avoids the inf - inf = nan trap on fully-masked
# blocks (where the running max itself stays at NEG).
NEG = -1e30

# The softmax runs in base 2 (scores pre-scaled by log2 e) and the running
# max is kept on the INTEGER lattice (ceil), so the rescale factor
# alpha = 2^(m - m') is an exact power of two: rescaling the o/l carries is
# a pure exponent shift that never rounds their mantissas — every mantissa
# loss in the online accumulation is the modeled per-block carry rounding,
# exactly the regime the paper's VRR analysis prices.  It also makes the
# update order-robust at the bit level: a * 2^k is exactly representable,
# so fused (FMA) and separate multiply-add lower identically — which is
# what lets the Pallas kernels and the unfused jnp references agree
# bit-for-bit instead of to 1 ulp.
LOG2E = 1.4426950408889634

_WIDE = (8, 23)

# the softmax weights and scores are f32 values that bf16 cannot hold, so
# the in-block contractions ask for full f32 precision rather than the
# backend's default MXU passes (read at trace time)
PV_PRECISION = jax.lax.Precision.HIGHEST


def _pv(p: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """Batched ``probs @ values`` contraction in f32: p (..., G, T) with
    v (..., T, D) -> (..., G, D).  One helper shared by the kernels (2D
    operands) and the references (batched operands) so the ideal intra-block
    contraction is the same primitive in both, at ``PV_PRECISION``."""
    nb = p.ndim - 2
    batch = tuple(range(nb))
    return jax.lax.dot_general(
        p, v, (((p.ndim - 1,), (nb,)), (batch, batch)),
        precision=PV_PRECISION,
        preferred_element_type=jnp.float32)


# the l carry draws its dither from a salted seed stream so it never shares
# bits with the o carry of the same (row, block) — correlated dither between
# numerator and denominator would bias the finalized ratio
_L_SALT = 0x6A09E667


def _sr_attn_bits(seed, step, *, abs_row0, head0, block_q: int, dh: int,
                  h: int, shape3=None):
    """Dither bits for one KV-block carry update of the online softmax.

    Pure function of (seed, absolute KV-block index ``step``, absolute
    query row, head, feature) — invariant to q blocking, grid schedule and
    chunked-prefill resumption (a resumed walk re-derives the SAME bits the
    one-shot walk used at that block, so resume == one-shot stays bitwise).
    Returns ``(rbits_o, rbits_l)`` shaped like the o / l carries: the
    kernel calls it per (head, q-tile) with scalars ``head0``/``abs_row0``;
    the reference passes ``shape3=(h, s, dh)`` to draw the whole slab's
    bits in one shot from identical coordinates."""
    seed = jnp.asarray(seed).astype(jnp.uint32)
    step = jnp.asarray(step).astype(jnp.uint32)
    row0 = jnp.asarray(abs_row0).astype(jnp.uint32)
    if shape3 is None:
        head = jnp.asarray(head0).astype(jnp.uint32)
        ro = (jax.lax.broadcasted_iota(jnp.uint32, (block_q, dh), 0) + row0)
        co = (jax.lax.broadcasted_iota(jnp.uint32, (block_q, dh), 1)
              + head * jnp.uint32(dh))
        rl = (jax.lax.broadcasted_iota(jnp.uint32, (block_q, 1), 0) + row0)
        cl = jnp.broadcast_to(head, (block_q, 1))
    else:
        ro = jax.lax.broadcasted_iota(jnp.uint32, shape3, 1) + row0
        co = (jax.lax.broadcasted_iota(jnp.uint32, shape3, 0)
              * jnp.uint32(dh)
              + jax.lax.broadcasted_iota(jnp.uint32, shape3, 2))
        rl, cl = ro[..., :1], co[..., :1] // jnp.uint32(dh)
    rbits_o = sr_random_bits(seed, step, ro, co, h * dh)
    rbits_l = sr_random_bits(seed ^ jnp.uint32(_L_SALT), step, rl, cl, h)
    return rbits_o, rbits_l


def _online_update(o, m, l, t, valid, v, e_acc: int, m_acc: int,
                   rounding: str = "rne", rbits=None):
    """One KV-block step of the online softmax with the chunked
    low-precision carry discipline.

    ``o`` (..., G, D) / ``m``, ``l`` (..., G, 1) are the carries, ``t``
    (..., G, T) this block's BASE-2 scores (pre-scaled by log2 e, NEG where
    invalid), ``valid`` the score mask, ``v`` (..., T, D) the block's
    values.  The running max lives on the integer lattice so the rescale is
    an exact exponent shift (see LOG2E); the rescale-and-add of ``o`` and
    ``l`` is then rounded to (1, e_acc, m_acc) once per block — the
    inter-chunk stage of the paper's Corollary 1 — while everything within
    the block is ideal f32.  A fully-masked block is a carry no-op: alpha =
    2^0 = 1, the addends are exactly zero, and the carry is a representable
    point of the accumulator format, so quantize(c + 0) == c — under BOTH
    roundings (a representable point is a fixed point of the SR dither
    too, so predicating a provably-masked block away stays bit-identical
    to running it).  ``rounding="sr"`` replaces the carry's
    round-to-nearest with stochastic rounding driven by ``rbits``, a
    ``(rbits_o, rbits_l)`` pair from ``_sr_attn_bits``.  Returns
    (o', m', l')."""
    m_new = jnp.maximum(m, jnp.ceil(jnp.max(t, axis=-1, keepdims=True)))
    alpha = jnp.exp2(m - m_new)
    # exp2(t - m_new) would be 2^0 = 1 on fully-masked rows (t == m_new ==
    # NEG); the explicit mask keeps invalid columns at exactly 0
    p = jnp.where(valid, jnp.exp2(t - m_new), 0.0)
    l_raw = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
    o_raw = o * alpha + _pv(p, v)
    if rounding == "sr":
        rbits_o, rbits_l = rbits
        l_new = quantize_block_sr(l_raw, e_acc, m_acc, rbits_l)
        o_new = quantize_block_sr(o_raw, e_acc, m_acc, rbits_o)
    else:
        l_new = quantize_block(l_raw, e_acc, m_acc)
        o_new = quantize_block(o_raw, e_acc, m_acc)
    return o_new, m_new, l_new


def _finalize(o, l):
    """out = o / l; 0 where nothing was attended (l == 0)."""
    return jnp.where(l > 0.0, o / jnp.where(l > 0.0, l, 1.0), 0.0)


# --------------------------------------------------------------------------
# prefill
# --------------------------------------------------------------------------


def _prefill_kernel(*refs, sk_true: int, block_q: int, chunk: int,
                    e_acc: int, m_acc: int, scale: float, q_offset: int,
                    kv_offset: int, has_carry: bool, emit_carry: bool,
                    rounding: str, sr_seed: int, h_total: int):
    n_in = 6 if has_carry else 3
    q_ref, k_ref, v_ref = refs[:3]
    out_refs = refs[n_in:n_in + (3 if emit_carry else 1)]
    oacc, mx, lx = refs[n_in + (3 if emit_carry else 1):]
    # program_id must be bound at kernel top level (interpret mode only
    # substitutes it there, not inside pl.when branch jaxprs)
    hq, qi, kk = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        if has_carry:
            co_ref, cm_ref, cl_ref = refs[3:6]
            oacc[...] = co_ref[0]
            mx[...] = cm_ref[0]
            lx[...] = cl_ref[0]
        else:
            oacc[...] = jnp.zeros_like(oacc)
            mx[...] = jnp.full_like(mx, NEG)
            lx[...] = jnp.zeros_like(lx)

    # blocks strictly in the causal future (or wholly past the KV slab's
    # end) are provably carry no-ops — every score masked, alpha = 1,
    # addends exactly 0 — so their MXU/VPU work is predicated away outright.
    # Causality is on ABSOLUTE positions: query row i sits at q_offset + i,
    # KV column j at kv_offset + j (one-shot calls have both offsets 0).
    @pl.when((kv_offset + kk * chunk
              <= q_offset + qi * block_q + block_q - 1)
             & (kk * chunk < sk_true))
    def _update():
        q = q_ref[0]  # (block_q, dh)
        k = k_ref[0]  # (chunk, dh)
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        rows = (q_offset + qi * block_q
                + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0))
        cols_l = kk * chunk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = (kv_offset + cols_l <= rows) & (cols_l < sk_true)
        s = jnp.where(valid, s, NEG)
        rbits = None
        if rounding == "sr":
            # dither keyed on the ABSOLUTE kv-block index and absolute
            # (row, head, feature) — a resumed walk draws the same bits
            rbits = _sr_attn_bits(
                jnp.uint32(sr_seed), kv_offset // chunk + kk,
                abs_row0=q_offset + qi * block_q, head0=hq,
                block_q=block_q, dh=v.shape[-1], h=h_total)
        o_new, m_new, l_new = _online_update(
            oacc[...], mx[...], lx[...], s, valid, v, e_acc, m_acc,
            rounding=rounding, rbits=rbits)
        oacc[...] = o_new
        mx[...] = m_new
        lx[...] = l_new

    @pl.when(kk == pl.num_programs(2) - 1)
    def _emit():
        if emit_carry:
            out_refs[0][0] = oacc[...]
            out_refs[1][0] = mx[...]
            out_refs[2][0] = lx[...]
        else:
            out_refs[0][0] = _finalize(oacc[...], lx[...])


@functools.partial(
    jax.jit,
    static_argnames=("e_acc", "m_acc", "chunk", "block_q", "q_offset",
                     "kv_offset", "emit_carry", "interpret", "rounding",
                     "sr_seed"),
)
def _flash_prefill(q, k, v, carry_o, carry_m, carry_l, *, e_acc, m_acc,
                   chunk, block_q, q_offset, kv_offset, emit_carry,
                   interpret, rounding="rne", sr_seed=0):
    _count_trace("flash_prefill")
    s, h, dh = q.shape
    sk_true = k.shape[0]
    kv = k.shape[1]
    g = h // kv
    has_carry = carry_o is not None
    # GQA: repeat K/V to the full head count (prefill-transient HBM; the
    # decode kernel instead shares one KV page across its g query rows)
    kh = jnp.repeat(k, g, axis=1) if g > 1 else k
    vh = jnp.repeat(v, g, axis=1) if g > 1 else v
    sq = -(-s // block_q) * block_q
    sk = -(-sk_true // chunk) * chunk
    qt = jnp.pad(q.astype(jnp.float32).transpose(1, 0, 2),
                 ((0, 0), (0, sq - s), (0, 0)))
    kt = jnp.pad(kh.astype(jnp.float32).transpose(1, 0, 2),
                 ((0, 0), (0, sk - sk_true), (0, 0)))
    vt = jnp.pad(vh.astype(jnp.float32).transpose(1, 0, 2),
                 ((0, 0), (0, sk - sk_true), (0, 0)))
    grid = (h, sq // block_q, sk // chunk)
    in_specs = [
        pl.BlockSpec((1, block_q, dh), lambda hh, qi, kk: (hh, qi, 0)),
        pl.BlockSpec((1, chunk, dh), lambda hh, qi, kk: (hh, kk, 0)),
        pl.BlockSpec((1, chunk, dh), lambda hh, qi, kk: (hh, kk, 0)),
    ]
    operands = [qt, kt, vt]
    if has_carry:
        # carry rows ride in the kernel layout; padded rows get the same
        # neutral state the cold init uses (they are sliced off anyway)
        co = jnp.pad(carry_o.astype(jnp.float32).transpose(1, 0, 2),
                     ((0, 0), (0, sq - s), (0, 0)))
        cm = jnp.pad(carry_m.astype(jnp.float32).T[..., None],
                     ((0, 0), (0, sq - s), (0, 0)), constant_values=NEG)
        cl = jnp.pad(carry_l.astype(jnp.float32).T[..., None],
                     ((0, 0), (0, sq - s), (0, 0)))
        operands += [co, cm, cl]
        in_specs += [
            pl.BlockSpec((1, block_q, dh), lambda hh, qi, kk: (hh, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda hh, qi, kk: (hh, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda hh, qi, kk: (hh, qi, 0)),
        ]
    o_spec = pl.BlockSpec((1, block_q, dh), lambda hh, qi, kk: (hh, qi, 0))
    o_shape = jax.ShapeDtypeStruct((h, sq, dh), jnp.float32)
    if emit_carry:
        s_spec = pl.BlockSpec((1, block_q, 1), lambda hh, qi, kk: (hh, qi, 0))
        s_shape = jax.ShapeDtypeStruct((h, sq, 1), jnp.float32)
        out_specs: list | pl.BlockSpec = [o_spec, s_spec, s_spec]
        out_shape: list | jax.ShapeDtypeStruct = [o_shape, s_shape, s_shape]
    else:
        out_specs, out_shape = o_spec, o_shape
    out = pl.pallas_call(
        functools.partial(_prefill_kernel, sk_true=sk_true, block_q=block_q,
                          chunk=chunk, e_acc=e_acc, m_acc=m_acc,
                          scale=LOG2E / math.sqrt(dh), q_offset=q_offset,
                          kv_offset=kv_offset, has_carry=has_carry,
                          emit_carry=emit_carry, rounding=rounding,
                          sr_seed=sr_seed, h_total=h),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, dh), jnp.float32),  # o carry
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max (exact)
            pltpu.VMEM((block_q, 1), jnp.float32),   # l carry
        ],
        interpret=interpret,
    )(*operands)
    if emit_carry:
        o, m, l = out
        return (o.transpose(1, 0, 2)[:s], m[..., 0].T[:s], l[..., 0].T[:s])
    return out.transpose(1, 0, 2)[:s]


@register_kernel("flash_prefill")
def flash_prefill(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    acc: tuple[int, int] = _WIDE,
    chunk: int = 128,
    block_q: int = 128,
    q_offset: int = 0,
    kv_offset: int = 0,
    carry: tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray] | None = None,
    return_carry: bool = False,
    call: AttnCall | None = None,
    interpret: bool | None = None,
    rounding: str = "rne",
    sr_seed: int = 0,
) -> jnp.ndarray | tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Causal flash attention for one sequence's prefill (resumable).

    * ``q`` (S, H, dh) — query rows at absolute positions ``q_offset + i``;
      ``k``/``v`` (Sk, KV, dh) — KV rows at absolute positions
      ``kv_offset + j`` (GQA handled by head repetition).  Values should
      already carry the KV-cache quantization
      (``repro.serve.kvcache.write_prompt`` returns the dequantized view)
      so that later paged decode attends to exactly what prefill attended.
    * ``acc`` — the (e_acc, m_acc) carry format from the serve planner.
    * ``chunk`` is the KV block length n1 — numerics (the carry rounding
      cadence; the serve path pins it to the KV page size so prefill and
      decode share one accumulation geometry).  ``block_q`` is
      schedule-only: any choice is bit-identical (each query row's block
      sequence over KV is fixed), tuned via ``autotune_flash_prefill``.
    * ``carry`` — a previous call's ``(o, m, l)`` state (shapes (S, H, dh),
      (S, H), (S, H)) covering KV ``[0, kv_offset)``; ``return_carry=True``
      returns the raw state after this call's KV instead of the finalized
      output.  Resuming at a ``chunk`` multiple is bit-identical to the
      one-shot walk: the o/l carries are representable accumulator-format
      points and the running max is on the integer lattice, so the HBM
      round-trip is exact.  Offsets are static (one trace per slab
      geometry — the serve engine's slab sizes are fixed per plan).
    * ``call`` — an ``AttnCall`` spec supplying acc/chunk/block_q/offsets
      in one struct (the same one the autotuner and the serve compile
      cache key on); explicit kwargs are ignored when it is given.
    * ``rounding="sr"`` — stochastic rounding of the o/l carries, seeded by
      ``sr_seed``: deterministic given the seed, block_q/schedule-invariant
      and resume==one-shot bitwise (the dither is keyed on the ABSOLUTE
      kv-block index and absolute row/head/feature coordinates, so a
      resumed walk re-derives the one-shot walk's bits).  Default "rne" is
      bit-identical to the seed-less kernel.
    """
    if rounding not in ROUNDINGS:
        raise ValueError(f"rounding must be one of {ROUNDINGS}")
    if call is not None:
        acc = call.acc
        chunk = call.chunk
        block_q = call.resolve_block_q()
        q_offset = call.q_offset
        kv_offset = call.kv_offset
        return_carry = bool(return_carry or call.return_carry)
    if q.ndim != 3 or k.ndim != 3 or v.ndim != 3 or k.shape != v.shape:
        raise ValueError(f"bad shapes q{q.shape} k{k.shape} v{v.shape}")
    if q.shape[1] % k.shape[1] != 0:
        raise ValueError(f"H={q.shape[1]} not a multiple of KV={k.shape[1]}")
    if kv_offset % chunk != 0:
        raise ValueError(
            f"kv_offset {kv_offset} must be a multiple of chunk {chunk}: a "
            "mid-block resumption would insert an extra carry-rounding "
            "event and break bit-exactness vs the one-shot walk")
    carry_o = carry_m = carry_l = None
    if carry is not None:
        carry_o, carry_m, carry_l = carry
        s, h, dh = q.shape
        if carry_o.shape != (s, h, dh) or carry_m.shape != (s, h) \
                or carry_l.shape != (s, h):
            raise ValueError(
                f"carry shapes {carry_o.shape}/{carry_m.shape}/"
                f"{carry_l.shape} do not match q {q.shape}")
    e_acc, m_acc = acc
    return _flash_prefill(q, k, v, carry_o, carry_m, carry_l,
                          e_acc=int(e_acc), m_acc=int(m_acc),
                          chunk=int(chunk), block_q=int(block_q),
                          q_offset=int(q_offset), kv_offset=int(kv_offset),
                          emit_carry=bool(return_carry),
                          interpret=resolve_interpret(interpret),
                          rounding=rounding, sr_seed=int(sr_seed))


def flash_prefill_reference(q, k, v, *, acc=_WIDE, chunk=128, q_offset=0,
                            kv_offset=0, carry=None, return_carry=False,
                            rounding="rne", sr_seed=0):
    """Unfused jnp oracle for ``flash_prefill``: same chunk walk, same carry
    rounding, no q blocking (per-row results are block_q-invariant).
    Mirrors the kernel's resumable-carry contract exactly — including the
    SR dither coordinates, so kernel and reference agree bitwise in both
    rounding modes."""
    s, h, dh = q.shape
    sk_true = k.shape[0]
    g = h // k.shape[1]
    kh = jnp.repeat(k, g, axis=1).astype(jnp.float32).transpose(1, 0, 2)
    vh = jnp.repeat(v, g, axis=1).astype(jnp.float32).transpose(1, 0, 2)
    qt = q.astype(jnp.float32).transpose(1, 0, 2)  # (h, s, dh)
    sk = -(-sk_true // chunk) * chunk
    kh = jnp.pad(kh, ((0, 0), (0, sk - sk_true), (0, 0)))
    vh = jnp.pad(vh, ((0, 0), (0, sk - sk_true), (0, 0)))
    e_acc, m_acc = acc
    if carry is None:
        o = jnp.zeros((h, s, dh), jnp.float32)
        m = jnp.full((h, s, 1), NEG, jnp.float32)
        l = jnp.zeros((h, s, 1), jnp.float32)
    else:
        co, cm, cl = carry
        o = co.astype(jnp.float32).transpose(1, 0, 2)
        m = cm.astype(jnp.float32).T[..., None]
        l = cl.astype(jnp.float32).T[..., None]
    rows = q_offset + jnp.arange(s)[None, :, None]
    scale = LOG2E / math.sqrt(dh)
    for kk in range(sk // chunk):
        kb = kh[:, kk * chunk:(kk + 1) * chunk]
        vb = vh[:, kk * chunk:(kk + 1) * chunk]
        sc = _pv(qt, kb.transpose(0, 2, 1)) * scale  # (h, s, chunk)
        cols_l = kk * chunk + jnp.arange(chunk)[None, None, :]
        valid = (kv_offset + cols_l <= rows) & (cols_l < sk_true)
        sc = jnp.where(valid, sc, NEG)
        rbits = None
        if rounding == "sr":
            rbits = _sr_attn_bits(jnp.uint32(sr_seed),
                                  kv_offset // chunk + kk,
                                  abs_row0=q_offset, head0=0,
                                  block_q=s, dh=dh, h=h,
                                  shape3=(h, s, dh))
        o, m, l = _online_update(o, m, l, sc, valid, vb, e_acc, m_acc,
                                 rounding=rounding, rbits=rbits)
    if return_carry:
        return (o.transpose(1, 0, 2), m[..., 0].T, l[..., 0].T)
    return _finalize(o, l).transpose(1, 0, 2)


# --------------------------------------------------------------------------
# paged decode
# --------------------------------------------------------------------------


def _page_values(ref, se_ref, pid, *, packed, e_kv, m_kv):
    """One KV page as f32 values in VMEM: unpack the int8 codes and apply
    the page's power-of-two scale exponent (from SMEM), or pass the f32
    carrier through (parity mode)."""
    x = ref[0, 0]  # (page_size, dh)
    if not packed:
        return x
    return unpack_block(x, e_kv, m_kv) * jnp.exp2(
        se_ref[pid].astype(jnp.float32))


def _dequant_block(x, se_ref, pids, *, packed, e_kv, m_kv):
    """A block of pages (len(pids), ..., page_size, dh) as a list of per-page
    f32 values: one unpack over the block, then each page's scale."""
    if not packed:
        return list(x)
    u = unpack_block(x, e_kv, m_kv)
    return [u[i] * jnp.exp2(se_ref[pid].astype(jnp.float32))
            for i, pid in enumerate(pids)]


# the serving decode kernel's block: the most pages that divide the page
# table's width and fit this many tokens make one grid step
_DECODE_BLOCK_TOKENS = 256


def _decode_block_pages(page_size: int, max_pages: int) -> int:
    """Pages per grid step of the serving decode kernel, from shapes alone:
    the most that divide ``max_pages`` within ``_DECODE_BLOCK_TOKENS``
    tokens (the whole table when it is narrower)."""
    cap = max(1, min(max_pages, _DECODE_BLOCK_TOKENS // page_size))
    return max(d for d in range(1, cap + 1) if max_pages % d == 0)


def _decode_kernel(pt_ref, sl_ref, kse_ref, vse_ref, q_ref, *refs, ppb,
                   manual, packed, e_kv, m_kv, e_acc, m_acc, page_size,
                   scale, emit_carry=False):
    """Grid (B, max_pages // ppb): one step walks ``ppb`` pages of one row
    for every KV head, and blocks wholly past the row's length fetch and
    compute nothing.  ``manual``: the arena stays in HBM and a live block's
    pages are copied (one contiguous (KV, page_size, dh) DMA each) into
    one slot of a double buffer while the block before it computes.
    Otherwise each of the block's pages is a grid operand of its own whose
    index map repeats the row's last live block past its length, so the
    pipeline fetches nothing there.  Within a block the unpack and the
    scores of all its pages are batched; the carries then fold page by
    page through ``_online_update``, rounded once per page as on the
    one-page grid, so the walk is bit-identical to it."""
    oacc, mx, lx = refs[-3:]
    b, j = pl.program_id(0), pl.program_id(1)
    n_blk = pl.num_programs(1)
    seq_len = sl_ref[b]
    blk_tokens = ppb * page_size
    kv, g = q_ref.shape[1], q_ref.shape[2]
    live = j * blk_tokens < seq_len

    @pl.when(j == 0)
    def _init():
        oacc[...] = jnp.zeros_like(oacc)
        mx[...] = jnp.full_like(mx, NEG)
        lx[...] = jnp.zeros_like(lx)

    if manual:
        k_hbm, v_hbm = refs[:2]
        out_refs = refs[2:-6]
        kbuf, vbuf, sem = refs[-6:-3]
        slot = jax.lax.rem(j, 2)

        def copies(blk, into):
            out = []
            for i in range(ppb):
                pid = pt_ref[b, blk * ppb + i]
                out.append(pltpu.make_async_copy(
                    k_hbm.at[pid], kbuf.at[into, i], sem.at[0, into]))
                out.append(pltpu.make_async_copy(
                    v_hbm.at[pid], vbuf.at[into, i], sem.at[1, into]))
            return out

        # a row's first block starts its own copy; every live block starts
        # the next one's, into the other slot, before it waits on its own
        @pl.when((j == 0) & live)
        def _fetch_first():
            for c in copies(j, slot):
                c.start()

        @pl.when((j + 1 < n_blk) & ((j + 1) * blk_tokens < seq_len))
        def _fetch_next():
            for c in copies(j + 1, 1 - slot):
                c.start()

        def load():
            for c in copies(j, slot):
                c.wait()
            return kbuf[slot], vbuf[slot]
    else:
        k_refs, v_refs = refs[:ppb], refs[ppb:2 * ppb]
        out_refs = refs[2 * ppb:-3]

        def load():
            return (jnp.concatenate([r[...] for r in k_refs]),
                    jnp.concatenate([r[...] for r in v_refs]))

    # blocks wholly past the sequence's length (the page-table padding of
    # a mixed-length batch, pointing at the null page) are provably carry
    # no-ops — predicate their work away
    @pl.when(live)
    def _update():
        kb, vb = load()  # (ppb, KV, page_size, dh) each
        pids = [pt_ref[b, j * ppb + i] for i in range(ppb)]
        ks = _dequant_block(kb, kse_ref, pids, packed=packed, e_kv=e_kv,
                            m_kv=m_kv)
        vs = _dequant_block(vb, vse_ref, pids, packed=packed, e_kv=e_kv,
                            m_kv=m_kv)
        # every head's keys of the block, (KV, ppb * page_size, dh), scored
        # against the head's query rows in one contraction
        s = jax.lax.dot_general(
            q_ref[0], jnp.concatenate(ks, axis=1),
            (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale
        o, m, l = oacc[...], mx[...], lx[...]
        tok = j * blk_tokens + jax.lax.broadcasted_iota(
            jnp.int32, (kv, g, page_size), 2)
        for i in range(ppb):
            valid = tok + i * page_size < seq_len
            t = jnp.where(valid, s[..., i * page_size:(i + 1) * page_size],
                          NEG)
            o, m, l = _online_update(o, m, l, t, valid, vs[i], e_acc, m_acc)
        oacc[...] = o
        mx[...] = m
        lx[...] = l

    @pl.when(j == n_blk - 1)
    def _emit():
        if emit_carry:
            # raw carry out: the cross-shard merge (psum_carry) owns the
            # finalize — emitting (o, m, l) unfinalized keeps the merge an
            # exact exponent-shift combine
            out_refs[0][0] = oacc[...]
            out_refs[1][0] = mx[...]
            out_refs[2][0] = lx[...]
        else:
            out_refs[0][0] = _finalize(oacc[...], lx[...])


def _decode_kernel_stats(pt_ref, sl_ref, kse_ref, vse_ref, q_ref, k_ref,
                         v_ref, o_ref, stats_ref, oacc, mx, lx, oi, stats_acc,
                         *, packed, e_kv, m_kv, e_acc, m_acc, page_size,
                         scale):
    """Telemetry variant: the SAME online-softmax carries — identical
    values, identical order — plus a wide (f32) shadow ``o`` accumulation
    and the (1, N_STATS) swamping reduction over the output ensemble (the
    softmax-weighted value sums, the serve path's long accumulation).
    Output is bit-identical to ``_decode_kernel``.  Unlike the serving
    kernel this variant does NOT predicate away beyond-length pages: the
    ensemble moments are sampled on the LAST grid page (``emit_out``),
    which for a short sequence is a masked one — the probe pays the full
    grid, which is fine off the serving hot path."""
    b, hk, p = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    last_p = p == pl.num_programs(2) - 1

    @pl.when((b == 0) & (hk == 0) & (p == 0))
    def _init_stats():
        stats_acc[...] = jnp.zeros_like(stats_acc)

    @pl.when(p == 0)
    def _init():
        oacc[...] = jnp.zeros_like(oacc)
        mx[...] = jnp.full_like(mx, NEG)
        lx[...] = jnp.zeros_like(lx)
        oi[...] = jnp.zeros_like(oi)

    pid = pt_ref[b, p]
    k = _page_values(k_ref, kse_ref, pid, packed=packed, e_kv=e_kv, m_kv=m_kv)
    v = _page_values(v_ref, vse_ref, pid, packed=packed, e_kv=e_kv, m_kv=m_kv)
    q = q_ref[0, 0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    tok = p * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = tok < sl_ref[b]
    s = jnp.where(valid, s, NEG)

    prev_o, prev_m, prev_l = oacc[...], mx[...], lx[...]
    o_new, m_new, l_new = _online_update(
        prev_o, prev_m, prev_l, s, valid, v, e_acc, m_acc)
    oacc[...] = o_new
    mx[...] = m_new
    lx[...] = l_new
    # wide shadow: the ideal accumulation of the SAME rescaled addends
    # (base-2, integer-lattice max — identical to _online_update's)
    alpha = jnp.exp2(prev_m - m_new)
    pexp = jnp.where(valid, jnp.exp2(s - m_new), 0.0)
    pv = _pv(pexp, v)
    ideal = oi[...] * alpha + pv
    oi[...] = ideal

    mask = jnp.broadcast_to(sl_ref[b] > 0, o_new.shape)
    delta, step_max = stats_delta_row(o_new, prev_o * alpha, ideal, pv, mask,
                                      last_p)
    stats_update(stats_acc, delta[None, :], step_max[None])

    @pl.when(last_p)
    def _emit():
        o_ref[0, 0] = _finalize(oacc[...], lx[...])

    @pl.when((b == pl.num_programs(0) - 1) & (hk == pl.num_programs(1) - 1)
             & last_p)
    def _emit_stats():
        stats_ref[...] = stats_acc[...]


@functools.partial(
    jax.jit,
    static_argnames=("packed", "e_kv", "m_kv", "e_acc", "m_acc",
                     "collect_stats", "return_carry", "interpret"),
)
def _paged_decode(q4, k_pages, v_pages, k_se, v_se, page_table, seq_lens, *,
                  packed, e_kv, m_kv, e_acc, m_acc, collect_stats,
                  return_carry, interpret):
    _count_trace("paged_attn_decode")
    b, kv, g, dh = q4.shape
    page_size = k_pages.shape[2]
    max_pages = page_table.shape[1]
    kw = dict(packed=packed, e_kv=e_kv, m_kv=m_kv, e_acc=e_acc, m_acc=m_acc,
              page_size=page_size, scale=LOG2E / math.sqrt(dh))
    if collect_stats:
        # scalar-prefetch operands (SMEM): page table, lengths, page scale
        # exponents — the index maps gather each sequence's pages through
        # them, one page per grid step
        grid = (b, kv, max_pages)
        row = lambda bb, hk, p, pt, sl, ks, vs: (bb, hk, 0, 0)
        page = lambda bb, hk, p, pt, sl, ks, vs: (pt[bb, p], hk, 0, 0)
        in_specs = [pl.BlockSpec((1, 1, g, dh), row),
                    pl.BlockSpec((1, 1, page_size, dh), page),
                    pl.BlockSpec((1, 1, page_size, dh), page)]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=grid, in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, 1, g, dh), row),
                pl.BlockSpec((1, N_STATS),
                             lambda bb, hk, p, pt, sl, ks, vs: (0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((g, dh), jnp.float32),  # o carry
                pltpu.VMEM((g, 1), jnp.float32),   # running max (exact)
                pltpu.VMEM((g, 1), jnp.float32),   # l carry
                pltpu.VMEM((g, dh), jnp.float32),      # ideal o shadow
                pltpu.VMEM((1, N_STATS), jnp.float32),  # stats row
            ],
        )
        out, stats = pl.pallas_call(
            functools.partial(_decode_kernel_stats, **kw),
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((b, kv, g, dh), jnp.float32),
                       jax.ShapeDtypeStruct((1, N_STATS), jnp.float32)],
            interpret=interpret,
        )(page_table, seq_lens, k_se, v_se, q4, k_pages, v_pages)
        return out, stats[0]

    ppb = _decode_block_pages(page_size, max_pages)
    # the kernel's own copies slice the arena by page, which Mosaic allows
    # only when a page row fills whole lanes; narrower heads take the pages
    # as pipelined grid operands instead (same blocks, same folds)
    manual = dh % LANES == 0
    row = lambda bb, j, pt, sl, ks, vs: (bb, 0, 0, 0)
    if manual:
        page_specs = [pl.BlockSpec(memory_space=pl.ANY)] * 2
        operands = [k_pages, v_pages]
    else:
        blk_tokens = ppb * page_size

        def page_map(i):
            def index(bb, j, pt, sl, ks, vs):
                last = jnp.maximum(sl[bb] - 1, 0) // blk_tokens
                return (pt[bb, jnp.minimum(j, last) * ppb + i], 0, 0, 0)
            return index

        page_specs = [pl.BlockSpec((1, kv, page_size, dh), page_map(i))
                      for i in range(ppb)] * 2
        operands = [k_pages] * ppb + [v_pages] * ppb
    in_specs = [pl.BlockSpec((1, kv, g, dh), row)] + page_specs
    o_spec = pl.BlockSpec((1, kv, g, dh), row)
    o_shape = jax.ShapeDtypeStruct((b, kv, g, dh), jnp.float32)
    if return_carry:
        c_spec = pl.BlockSpec((1, kv, g, 1), row)
        c_shape = jax.ShapeDtypeStruct((b, kv, g, 1), jnp.float32)
        out_specs: list | pl.BlockSpec = [o_spec, c_spec, c_spec]
        out_shape: list | jax.ShapeDtypeStruct = [o_shape, c_shape, c_shape]
    else:
        out_specs, out_shape = o_spec, o_shape
    scratch = []
    if manual:
        buf = (2, ppb, kv, page_size, dh)  # double buffer of a block's pages
        scratch = [pltpu.VMEM(buf, k_pages.dtype),
                   pltpu.VMEM(buf, v_pages.dtype),
                   pltpu.SemaphoreType.DMA((2, 2))]  # (k | v, slot)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4, grid=(b, max_pages // ppb), in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch + [
            pltpu.VMEM((kv, g, dh), jnp.float32),  # o carry
            pltpu.VMEM((kv, g, 1), jnp.float32),   # running max (exact)
            pltpu.VMEM((kv, g, 1), jnp.float32),   # l carry
        ])
    return pl.pallas_call(
        functools.partial(_decode_kernel, ppb=ppb, manual=manual,
                          emit_carry=return_carry, **kw),
        grid_spec=grid_spec,
        out_shape=out_shape,
        # a row's blocks hand copies on to each other; rows are independent
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(page_table, seq_lens, k_se, v_se, q4, *operands)


@register_kernel("paged_attn_decode")
def paged_attn_decode(
    q: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    k_se: jnp.ndarray,
    v_se: jnp.ndarray,
    page_table: jnp.ndarray,
    seq_lens: jnp.ndarray,
    *,
    kv_fmt=None,
    acc: tuple[int, int] = _WIDE,
    collect_stats: bool = False,
    return_carry: bool = False,
    interpret: bool | None = None,
):
    """One decode token of attention per sequence against the paged cache.

    * ``q`` (B, H, dh) f32 — this step's query rows.
    * ``k_pages``/``v_pages`` (P, KV, page_size, dh) — the arena: int8
      ``(1, e, m)`` codes (``kv_fmt`` required; unpacked in VMEM) or f32
      carriers (parity/oracle mode, ``kv_fmt`` ignored for decoding).
    * ``k_se``/``v_se`` (P,) int32 — per-page power-of-two scale exponents
      (ignored in f32 mode: the carrier already includes the scale).
    * ``page_table`` (B, max_pages) int32 — page ids per sequence, padded
      with 0 (page 0 is the reserved null page, see ``serve.kvcache``).
    * ``seq_lens`` (B,) int32 — valid tokens per sequence (0 = inactive
      row: output is exactly 0 and nothing is attended).
    * ``acc`` — the (e_acc, m_acc) carry format for this context bucket
      (``repro.serve.plan``); the page size is the chunk length n1.
    * ``collect_stats=True`` additionally returns the raw (N_STATS,)
      swamping vector over the output ensemble (see module docstring).
    * ``return_carry=True`` skips the finalize and returns the raw
      online-softmax carry ``(o (B,H,dh), m (B,H), l (B,H))`` — the
      tensor-parallel merge combines per-shard carries with ``psum_carry``
      and finalizes once, globally.

    Returns (B, H, dh) f32 [, stats], or the carry triple.
    """
    if collect_stats and return_carry:
        raise ValueError("collect_stats and return_carry are exclusive")
    if q.ndim != 3:
        raise ValueError(f"q must be (B, H, dh), got {q.shape}")
    if k_pages.shape != v_pages.shape or k_pages.ndim != 4:
        raise ValueError(f"bad pages {k_pages.shape} / {v_pages.shape}")
    b, h, dh = q.shape
    kv = k_pages.shape[1]
    if h % kv != 0:
        raise ValueError(f"H={h} not a multiple of KV={kv}")
    packed = k_pages.dtype == jnp.int8
    fmt = fmt_tuple(kv_fmt)
    if packed and fmt is None:
        raise ValueError("packed pages need kv_fmt to decode")
    e_kv, m_kv = fmt or _WIDE
    # (B, H, dh) rows are kv-major: head hh = hk * g + gg belongs to kv
    # head hk — reshape (B, kv, g, dh) is exactly that grouping
    q4 = q.astype(jnp.float32).reshape(b, kv, h // kv, dh)
    e_acc, m_acc = acc
    out = _paged_decode(
        q4, k_pages, v_pages,
        jnp.asarray(k_se, jnp.int32), jnp.asarray(v_se, jnp.int32),
        jnp.asarray(page_table, jnp.int32), jnp.asarray(seq_lens, jnp.int32),
        packed=packed, e_kv=int(e_kv), m_kv=int(m_kv),
        e_acc=int(e_acc), m_acc=int(m_acc),
        collect_stats=collect_stats, return_carry=return_carry,
        interpret=resolve_interpret(interpret))
    if collect_stats:
        o, stats = out
        return o.reshape(b, h, dh), stats
    if return_carry:
        o, m, l = out
        return (o.reshape(b, h, dh), m[..., 0].reshape(b, h),
                l[..., 0].reshape(b, h))
    return out.reshape(b, h, dh)


def paged_attn_decode_reference(q, k_pages, v_pages, k_se, v_se, page_table,
                                seq_lens, *, kv_fmt=None, acc=_WIDE,
                                return_carry=False):
    """Unfused jnp oracle for ``paged_attn_decode``: gathers pages through
    the page table with plain indexing, dequantizes with the per-page
    scales, and walks the pages in the same order with the same carry
    rounding.  Bit-exact against the kernel."""
    b, h, dh = q.shape
    kv = k_pages.shape[1]
    g = h // kv
    page_size = k_pages.shape[2]
    packed = k_pages.dtype == jnp.int8
    fmt = fmt_tuple(kv_fmt)
    e_kv, m_kv = fmt or _WIDE
    e_acc, m_acc = acc
    q4 = q.astype(jnp.float32).reshape(b, kv, g, dh)
    o = jnp.zeros((b, kv, g, dh), jnp.float32)
    m = jnp.full((b, kv, g, 1), NEG, jnp.float32)
    l = jnp.zeros((b, kv, g, 1), jnp.float32)
    scale = LOG2E / math.sqrt(dh)
    seq_lens = jnp.asarray(seq_lens, jnp.int32)
    for p in range(page_table.shape[1]):
        pid = jnp.asarray(page_table, jnp.int32)[:, p]  # (B,)
        kb = k_pages[pid]  # (B, kv, page_size, dh)
        vb = v_pages[pid]
        if packed:
            kb = unpack_block(kb, e_kv, m_kv) * jnp.exp2(
                k_se[pid].astype(jnp.float32))[:, None, None, None]
            vb = unpack_block(vb, e_kv, m_kv) * jnp.exp2(
                v_se[pid].astype(jnp.float32))[:, None, None, None]
        kb = kb.astype(jnp.float32)
        vb = vb.astype(jnp.float32)
        s = _pv(q4, kb.transpose(0, 1, 3, 2)) * scale  # (B, kv, g, page_size)
        tok = p * page_size + jnp.arange(page_size)[None, None, None, :]
        valid = tok < seq_lens[:, None, None, None]
        s = jnp.where(valid, s, NEG)
        o, m, l = _online_update(o, m, l, s, valid, vb, e_acc, m_acc)
    if return_carry:
        return (o.reshape(b, h, dh), m[..., 0].reshape(b, h),
                l[..., 0].reshape(b, h))
    return _finalize(o, l).reshape(b, h, dh)


# --------------------------------------------------------------------------
# cross-shard carry merge (tensor-parallel serving)
# --------------------------------------------------------------------------


def psum_carry(o, m, l, axis_name):
    """Merge per-shard online-softmax carries across a mesh axis.

    ``o`` is ``(..., dh)``; ``m``/``l`` are ``o``'s shape minus the last
    dim.  The global max ``m_g = pmax(m)`` stays on the integer lattice
    (each shard's running max already is), so every rescale factor
    ``alpha = 2^(m - m_g)`` is an exact power of two — the merge never
    rounds a carry mantissa, the same discipline as the in-kernel rescale.

    Head-sharded serving is the bit-exact special case: exactly one shard
    holds a non-neutral carry per (row, head) and every other shard holds
    the neutral element ``(o=0, m=NEG, l=0)``.  Then ``m_g`` is the
    owner's max bit-for-bit, the owner's alpha is ``2^0 = 1.0``, a
    non-owner's alpha is ``2^(NEG - m_g)`` which underflows to exactly
    ``+0.0`` (finite ``NEG``, see above), and the psums add exact zeros —
    the merged carry equals the owner's carry bitwise.
    """
    m_g = jax.lax.pmax(m, axis_name)
    alpha = jnp.exp2(m - m_g)
    o = jax.lax.psum(o * alpha[..., None], axis_name)
    l = jax.lax.psum(l * alpha, axis_name)
    return o, m_g, l


def merge_carries(carries):
    """Host/jnp oracle for ``psum_carry``: fold a list of carry triples
    into one with the same exponent-shift rescale.  With neutral-element
    non-owners (the head-sharded case) the fold is exact regardless of
    order — ``tests/test_serve_sharded.py`` fuzzes merge order against
    this."""
    o, m, l = carries[0]
    for o2, m2, l2 in carries[1:]:
        m_new = jnp.maximum(m, m2)
        a1 = jnp.exp2(m - m_new)
        a2 = jnp.exp2(m2 - m_new)
        o = o * a1[..., None] + o2 * a2[..., None]
        l = l * a1 + l2 * a2
        m = m_new
    return o, m, l


def finalize_carry(o, l):
    """Normalize a merged carry: ``o / l`` where attended, exact 0 where
    nothing was (``l == 0``).  Identical to the kernels' in-VMEM
    finalize."""
    return _finalize(o, l[..., None])


# --------------------------------------------------------------------------
# bucketed paged prefill — one compiled kernel per attention bucket
# --------------------------------------------------------------------------


def _prefill_paged_kernel(pr_ref, gm_ref, kse_ref, vse_ref, *refs,
                          block_q: int, page_size: int, packed: bool,
                          e_kv: int, m_kv: int, e_acc: int, m_acc: int,
                          scale: float, has_carry: bool, emit_carry: bool):
    """Grid (H, q_blocks, max_pages).  The page row and the slab geometry
    (``gm_ref`` = [q_offset, q_len, kv_len, start_page], SMEM) are traced
    scalar-prefetch operands, so every slab of every prompt in the bucket
    reuses this one compiled body; pages past the live count, before the
    carry's resume point, or wholly in the causal future are provable
    carry no-ops and are predicated away."""
    n_in = 6 if has_carry else 3
    q_ref, k_ref, v_ref = refs[:3]
    out_refs = refs[n_in:n_in + (3 if emit_carry else 1)]
    oacc, mx, lx = refs[n_in + (3 if emit_carry else 1):]
    qi, p = pl.program_id(1), pl.program_id(2)
    q_off, q_len, kv_len, start_pg = (gm_ref[0], gm_ref[1], gm_ref[2],
                                      gm_ref[3])

    @pl.when(p == 0)
    def _init():
        if has_carry:
            co_ref, cm_ref, cl_ref = refs[3:6]
            oacc[...] = co_ref[0]
            mx[...] = cm_ref[0]
            lx[...] = cl_ref[0]
        else:
            oacc[...] = jnp.zeros_like(oacc)
            mx[...] = jnp.full_like(mx, NEG)
            lx[...] = jnp.zeros_like(lx)

    @pl.when((p >= start_pg) & (p * page_size < kv_len)
             & (p * page_size <= q_off + qi * block_q + block_q - 1))
    def _update():
        pid = pr_ref[p]
        k = _page_values(k_ref, kse_ref, pid, packed=packed, e_kv=e_kv,
                         m_kv=m_kv)
        v = _page_values(v_ref, vse_ref, pid, packed=packed, e_kv=e_kv,
                         m_kv=m_kv)
        q = q_ref[0]  # (block_q, dh)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        rows = (q_off + qi * block_q
                + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0))
        rloc = (qi * block_q
                + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0))
        cols = (p * page_size
                + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
        valid = (cols <= rows) & (cols < kv_len) & (rloc < q_len)
        s = jnp.where(valid, s, NEG)
        o_new, m_new, l_new = _online_update(
            oacc[...], mx[...], lx[...], s, valid, v, e_acc, m_acc)
        oacc[...] = o_new
        mx[...] = m_new
        lx[...] = l_new

    @pl.when(p == pl.num_programs(2) - 1)
    def _emit():
        if emit_carry:
            out_refs[0][0] = oacc[...]
            out_refs[1][0] = mx[...]
            out_refs[2][0] = lx[...]
        else:
            out_refs[0][0] = _finalize(oacc[...], lx[...])


@functools.partial(
    jax.jit,
    static_argnames=("packed", "e_kv", "m_kv", "e_acc", "m_acc", "block_q",
                     "emit_carry", "interpret"),
)
def _flash_prefill_paged(q, k_pages, v_pages, k_se, v_se, page_row, geom,
                         carry_o, carry_m, carry_l, *, packed, e_kv, m_kv,
                         e_acc, m_acc, block_q, emit_carry, interpret):
    _count_trace("flash_prefill_paged")
    t, h, dh = q.shape
    kv = k_pages.shape[1]
    g = h // kv
    page_size = k_pages.shape[2]
    max_pages = page_row.shape[0]
    has_carry = carry_o is not None
    sq = -(-t // block_q) * block_q
    qt = jnp.pad(q.astype(jnp.float32).transpose(1, 0, 2),
                 ((0, 0), (0, sq - t), (0, 0)))
    grid = (h, sq // block_q, max_pages)
    # GQA rides the index map: query head hh reads KV head hh // g straight
    # from the arena — no repeated HBM copy (the dense kernel's jnp.repeat)
    in_specs = [
        pl.BlockSpec((1, block_q, dh),
                     lambda hh, qi, p, pr, gm, ks, vs: (hh, qi, 0)),
        pl.BlockSpec((1, 1, page_size, dh),
                     lambda hh, qi, p, pr, gm, ks, vs, g=g:
                     (pr[p], hh // g, 0, 0)),
        pl.BlockSpec((1, 1, page_size, dh),
                     lambda hh, qi, p, pr, gm, ks, vs, g=g:
                     (pr[p], hh // g, 0, 0)),
    ]
    operands = [qt, k_pages, v_pages]
    if has_carry:
        co = jnp.pad(carry_o.astype(jnp.float32).transpose(1, 0, 2),
                     ((0, 0), (0, sq - t), (0, 0)))
        cm = jnp.pad(carry_m.astype(jnp.float32).T[..., None],
                     ((0, 0), (0, sq - t), (0, 0)), constant_values=NEG)
        cl = jnp.pad(carry_l.astype(jnp.float32).T[..., None],
                     ((0, 0), (0, sq - t), (0, 0)))
        operands += [co, cm, cl]
        in_specs += [
            pl.BlockSpec((1, block_q, dh),
                         lambda hh, qi, p, pr, gm, ks, vs: (hh, qi, 0)),
            pl.BlockSpec((1, block_q, 1),
                         lambda hh, qi, p, pr, gm, ks, vs: (hh, qi, 0)),
            pl.BlockSpec((1, block_q, 1),
                         lambda hh, qi, p, pr, gm, ks, vs: (hh, qi, 0)),
        ]
    o_spec = pl.BlockSpec((1, block_q, dh),
                          lambda hh, qi, p, pr, gm, ks, vs: (hh, qi, 0))
    o_shape = jax.ShapeDtypeStruct((h, sq, dh), jnp.float32)
    if emit_carry:
        s_spec = pl.BlockSpec((1, block_q, 1),
                              lambda hh, qi, p, pr, gm, ks, vs: (hh, qi, 0))
        s_shape = jax.ShapeDtypeStruct((h, sq, 1), jnp.float32)
        out_specs: list | pl.BlockSpec = [o_spec, s_spec, s_spec]
        out_shape: list | jax.ShapeDtypeStruct = [o_shape, s_shape, s_shape]
    else:
        out_specs, out_shape = o_spec, o_shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4, grid=grid, in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((block_q, dh), jnp.float32),  # o carry
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max (exact)
            pltpu.VMEM((block_q, 1), jnp.float32),   # l carry
        ])
    out = pl.pallas_call(
        functools.partial(_prefill_paged_kernel, block_q=block_q,
                          page_size=page_size, packed=packed, e_kv=e_kv,
                          m_kv=m_kv, e_acc=e_acc, m_acc=m_acc,
                          scale=LOG2E / math.sqrt(dh), has_carry=has_carry,
                          emit_carry=emit_carry),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(page_row, geom, k_se, v_se, *operands)
    if emit_carry:
        o, m, l = out
        return (o.transpose(1, 0, 2)[:t], m[..., 0].T[:t], l[..., 0].T[:t])
    return out.transpose(1, 0, 2)[:t]


@register_kernel("flash_prefill_paged")
def flash_prefill_paged(
    q: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    k_se: jnp.ndarray,
    v_se: jnp.ndarray,
    page_row: jnp.ndarray,
    q_offset,
    q_len,
    kv_len,
    *,
    kv_fmt=None,
    acc: tuple[int, int] = _WIDE,
    block_q: int = 128,
    carry: tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray] | None = None,
    start_page=0,
    return_carry: bool = False,
    call: AttnCall | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray | tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Bucketed causal prefill straight off the paged KV arena.

    One compiled instance serves every slab of every prompt in an
    attention bucket: the compiled signature depends only on the slab
    width T, the arena geometry and ``page_row``'s padded width (the
    bucket's ``max_pages``) — everything else is a traced operand.

    * ``q`` (T, H, dh) — the query slab, padded to the bucket's slab width;
      rows ``>= q_len`` are padding (their output is exactly 0).
    * ``k_pages``/``v_pages`` (P, KV, page_size, dh) + ``k_se``/``v_se``
      (P,) int32 — one layer's arena AFTER the slab's
      ``kvcache.write_prompt``: history and fresh slab are walked in one
      pass, int8 pages unpacked in VMEM exactly like ``paged_attn_decode``
      (f32 carriers pass through; ``kv_fmt`` ignored then).
    * ``page_row`` (max_pages,) int32 — this sequence's pages in token
      order, padded with 0 (the reserved null page); pages at positions
      ``>= ceil(kv_len / page_size)`` are never read.
    * ``q_offset``/``q_len``/``kv_len`` — traced int32 scalars: absolute
      position of q row 0, live query rows, total live KV tokens
      (history + slab).  Causality is on absolute positions, so a slab at
      any ``q_offset`` reuses the same executable.
    * ``carry``/``start_page``/``return_carry`` — resumable online-softmax
      state exactly as in ``flash_prefill``: ``carry`` covers KV pages
      ``[0, start_page)`` and the walk resumes there; the carry
      round-trips exactly (accumulator-format points + integer-lattice
      max), so split-anywhere equals one-shot bit-for-bit.
    * ``acc``/``block_q``/``call`` — carry format and the schedule-only q
      tile; ``call`` (an ``AttnCall`` with ``max_pages > 0``) supplies
      acc/block_q/kv_fmt from the one struct the serve compile cache and
      autotuner share.

    Returns (T, H, dh) f32, or the raw ``(o, m, l)`` carry.
    """
    if call is not None:
        acc = call.acc
        block_q = call.resolve_block_q()
        kv_fmt = call.kv_fmt
        return_carry = bool(return_carry or call.return_carry)
        if call.max_pages and page_row.shape[0] != call.max_pages:
            raise ValueError(
                f"page_row width {page_row.shape[0]} != bucket max_pages "
                f"{call.max_pages}")
    if q.ndim != 3:
        raise ValueError(f"q must be (T, H, dh), got {q.shape}")
    if k_pages.shape != v_pages.shape or k_pages.ndim != 4:
        raise ValueError(f"bad pages {k_pages.shape} / {v_pages.shape}")
    t, h, dh = q.shape
    kv = k_pages.shape[1]
    if h % kv != 0:
        raise ValueError(f"H={h} not a multiple of KV={kv}")
    packed = k_pages.dtype == jnp.int8
    fmt = fmt_tuple(kv_fmt)
    if packed and fmt is None:
        raise ValueError("packed pages need kv_fmt to decode")
    e_kv, m_kv = fmt or _WIDE
    carry_o = carry_m = carry_l = None
    if carry is not None:
        carry_o, carry_m, carry_l = carry
        if carry_o.shape != (t, h, dh) or carry_m.shape != (t, h) \
                or carry_l.shape != (t, h):
            raise ValueError(
                f"carry shapes {carry_o.shape}/{carry_m.shape}/"
                f"{carry_l.shape} do not match q {q.shape}")
    e_acc, m_acc = acc
    geom = jnp.stack([jnp.asarray(q_offset, jnp.int32),
                      jnp.asarray(q_len, jnp.int32),
                      jnp.asarray(kv_len, jnp.int32),
                      jnp.asarray(start_page, jnp.int32)])
    return _flash_prefill_paged(
        q, k_pages, v_pages,
        jnp.asarray(k_se, jnp.int32), jnp.asarray(v_se, jnp.int32),
        jnp.asarray(page_row, jnp.int32), geom, carry_o, carry_m, carry_l,
        packed=packed, e_kv=int(e_kv), m_kv=int(m_kv),
        e_acc=int(e_acc), m_acc=int(m_acc), block_q=int(block_q),
        emit_carry=bool(return_carry),
        interpret=resolve_interpret(interpret))


def flash_prefill_paged_reference(q, k_pages, v_pages, k_se, v_se, page_row,
                                  q_offset, q_len, kv_len, *, kv_fmt=None,
                                  acc=_WIDE, carry=None, start_page=0,
                                  return_carry=False,
                                  call: AttnCall | None = None):
    """Unfused jnp oracle for ``flash_prefill_paged``: gathers each page
    through the page row, dequantizes with the per-page scales, and walks
    ALL ``max_pages`` positions in order — pages the kernel predicates away
    are run fully masked here, which is a provable carry no-op (alpha = 1,
    addends exactly 0, the running max pinned at NEG), so oracle == kernel
    bit-for-bit."""
    if call is not None:
        acc = call.acc
        kv_fmt = call.kv_fmt
        return_carry = bool(return_carry or call.return_carry)
    t, h, dh = q.shape
    kv = k_pages.shape[1]
    g = h // kv
    page_size = k_pages.shape[2]
    packed = k_pages.dtype == jnp.int8
    fmt = fmt_tuple(kv_fmt)
    e_kv, m_kv = fmt or _WIDE
    e_acc, m_acc = acc
    qt = q.astype(jnp.float32).transpose(1, 0, 2)  # (h, t, dh)
    if carry is None:
        o = jnp.zeros((h, t, dh), jnp.float32)
        m = jnp.full((h, t, 1), NEG, jnp.float32)
        l = jnp.zeros((h, t, 1), jnp.float32)
    else:
        co, cm, cl = carry
        o = co.astype(jnp.float32).transpose(1, 0, 2)
        m = cm.astype(jnp.float32).T[..., None]
        l = cl.astype(jnp.float32).T[..., None]
    q_offset = jnp.asarray(q_offset, jnp.int32)
    q_len = jnp.asarray(q_len, jnp.int32)
    kv_len = jnp.asarray(kv_len, jnp.int32)
    start_page = jnp.asarray(start_page, jnp.int32)
    page_row = jnp.asarray(page_row, jnp.int32)
    rows = q_offset + jnp.arange(t)[None, :, None]
    rloc = jnp.arange(t)[None, :, None]
    scale = LOG2E / math.sqrt(dh)
    for p in range(page_row.shape[0]):
        pid = page_row[p]
        kb = k_pages[pid]  # (kv, page_size, dh)
        vb = v_pages[pid]
        if packed:
            kb = unpack_block(kb, e_kv, m_kv) * jnp.exp2(
                k_se[pid].astype(jnp.float32))
            vb = unpack_block(vb, e_kv, m_kv) * jnp.exp2(
                v_se[pid].astype(jnp.float32))
        kb = jnp.repeat(kb.astype(jnp.float32), g, axis=0)  # (h, page, dh)
        vb = jnp.repeat(vb.astype(jnp.float32), g, axis=0)
        sc = _pv(qt, kb.transpose(0, 2, 1)) * scale  # (h, t, page_size)
        cols = p * page_size + jnp.arange(page_size)[None, None, :]
        valid = ((cols <= rows) & (cols < kv_len) & (rloc < q_len)
                 & (p >= start_page))
        sc = jnp.where(valid, sc, NEG)
        o, m, l = _online_update(o, m, l, sc, valid, vb, e_acc, m_acc)
    if return_carry:
        return (o.transpose(1, 0, 2), m[..., 0].T, l[..., 0].T)
    return _finalize(o, l).transpose(1, 0, 2)
