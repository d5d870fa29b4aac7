"""Pallas TPU kernel: BOTH backward GEMMs of one dense layer in ONE pass.

The backward of y = Q(x) @ Q(w) runs two GEMMs that share the incoming
gradient g (paper Fig. 2):

    dx[T, K] = Q(g)[T, N] @ Q(w)^T[N, K]   (BWD  — accumulation length N)
    dw[K, N] = Q(x)^T[K, T] @ Q(g)[T, N]   (GRAD — accumulation length T,
                                            B*T tokens: the paper's critical
                                            long accumulation)

Run separately, g makes two full HBM round-trips and is
representation-quantized twice per use.  This kernel fuses the pair: one
grid (j over K, i over T, l over N); within each K-block sweep a g tile is
DMA'd once, quantized once on the VPU, and contracted twice on the MXU (g
is still revisited once per K-block, j being the outer axis — the same
revisit economics as the forward kernel's A-tiles), and the whole backward
of the layer is one pallas_call, cutting the qdot train step from 3 pallas
passes to 2.

Residual operands arrive exactly as the forward kernel emitted them —
int8-packed ``(1, e_r, m_r)`` codes (``repro.quant.qtensor`` layout) — and
are unpacked in VMEM; no standalone decode pass, and neither residual is
ever transposed in HBM (the contractions index x as [T, K] and w as [K, N]
directly via dot_general dimension numbers).

Chunked-accumulation semantics are IDENTICAL to the two separate fused
GEMMs, bit for bit:

* dx accumulates over the innermost grid axis l in a scratch tile, carry
  rounded to (1, e_bwd, m_bwd) once per N-chunk of the BWD chunk length
  n1, in the same N order as ``qmatmul_fused(g, w.T)``.
* dw accumulates over the middle axis i in a (block_k, N_padded) scratch
  slab, carry rounded to (1, e_grad, m_grad) once per T-chunk of the GRAD
  chunk length, in the same T order as ``qmatmul_fused(x.T, g)``.
* The T and N tiles are ``chunk_tile`` of the two chunk lengths (128 for
  any chunk that divides it) — the lane-aligned blocks the TPU can load —
  and each grid step folds its tile's chunks in order.
  The slab makes VMEM cost grow with N: ``pair_vmem_bytes`` prices it and
  ``repro.kernels.ops`` falls back to the two-call path when the budget
  (``repro.kernels.autotune.vmem_budget``) is exceeded.

dw blocks are emitted on the final T-chunk only (``pl.when(i == last)``) —
same single-write-per-block discipline as the forward residual emission,
with the same compiled-TPU copy-back caveat (see fused.py docstring).
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
from repro.kernels.fused import as_sr_seed
from repro.quant.qtensor import unpack_block

__all__ = ["qmatmul_bwd_pair", "qmatmul_bwd_pair_nsplit", "pair_vmem_bytes",
           "pair_segment_width"]

_WIDE = (8, 23)


def pair_segment_width(n: int, n_split: int, block_n: int) -> int:
    """Tile-aligned width of one N segment when splitting ``n`` columns
    into ``n_split`` segments (``block_n`` is the BWD chunk length; the
    tile is ``chunk_tile(block_n)``) — the single formula shared by the
    nsplit kernel wrapper, the VMEM gate
    (``repro.kernels.ops.pair_n_segments``) and the warmup autotuner, so
    tuned entries match the traced shapes."""
    tile = chunk_tile(block_n)
    raw = -(-n // n_split)
    return max(-(-raw // tile) * tile, tile)


def pair_vmem_bytes(block_t: int, block_k: int, block_n: int, n_padded: int,
                    *, packed: bool = True) -> int:
    """VMEM working set of one grid step: g/x/w tiles + dx/dw output tiles
    + dx carry tile + the (block_k, N_padded) dw carry slab."""
    opb = 1 if packed else 4  # residual operand tiles: int8 codes or f32
    tiles = (4 * block_t * block_n            # g tile (f32)
             + opb * block_t * block_k        # x residual tile
             + opb * block_k * block_n        # w residual tile
             + 4 * block_t * block_k          # dx out tile
             + 4 * block_k * block_n          # dw out tile
             + 4 * block_t * block_k)         # dx carry scratch
    return tiles + 4 * block_k * n_padded     # dw carry slab


# contraction dimension numbers of the two GEMMs: neither residual is
# transposed in memory
_DX_DIMS = (((1,), (1,)), ((), ()))  # g[t, n] . w[k, n] over n
_DW_DIMS = (((0,), (0,)), ((), ()))  # x[t, k] . g[t, n] over t


def _load_tiles(g_ref, x_ref, w_ref, *, qg, packed, e_r, m_r):
    """One VMEM landing of the g tile feeds BOTH contractions; quantized
    once per landing.  Residuals are unpacked in VMEM."""
    g = quantize_block(g_ref[...], e_r, m_r) if qg else g_ref[...]
    if packed:
        return g, unpack_block(x_ref[...], e_r, m_r), \
            unpack_block(w_ref[...], e_r, m_r)
    return g, x_ref[...], w_ref[...]


def _pair_kernel(*refs, e_r, m_r, qg, packed, e_bwd, m_bwd, e_grad, m_grad,
                 n1_bwd, n1_grad, rounding, k, n, resume=False, tile_off=0,
                 col_off=0):
    """Grid (j over K, i over T tiles, l over N tiles).  With ``resume``
    (one N segment of the split pair) the dx carry starts from ``dxc_ref``
    — the running dx of the previous segment — instead of zero; chaining
    segments in N order reproduces the unsplit accumulation bit-for-bit
    (the carries handed over are exact accumulator-format points, and the
    segment edges are tile-aligned).  ``tile_off``/``col_off`` place the
    segment's N tiles and dw columns on the global N axis, so split and
    unsplit draw identical SR bits."""
    if resume:
        g_ref, x_ref, w_ref, dxc_ref, *refs = refs
    else:
        g_ref, x_ref, w_ref, *refs = refs
    if rounding == "sr":
        sb_ref, sg_ref, dx_ref, dw_ref, dx_acc, dw_acc = refs
    else:
        dx_ref, dw_ref, dx_acc, dw_acc = refs
        sb_ref = sg_ref = None
    j = pl.program_id(0)
    i = pl.program_id(1)
    l = pl.program_id(2)
    block_t, block_k = dx_acc.shape
    block_n = dw_ref.shape[1]
    g, x, w = _load_tiles(g_ref, x_ref, w_ref, qg=qg, packed=packed,
                          e_r=e_r, m_r=m_r)

    # ---- dx: carry over l (innermost), chunk = n1_bwd, N order fixed ----
    @pl.when(l == 0)
    def _init_dx():
        if resume:
            dx_acc[...] = dxc_ref[...]
        else:
            dx_acc[...] = jnp.zeros_like(dx_acc)

    # SR coordinates mirror qmatmul_fused(g, w.T): dx element (t, k),
    # chunk step = the global N-chunk index
    dx_acc[...] = fold_chunks(
        dx_acc[...], g, w, _DX_DIMS, n1=n1_bwd, tile_idx=tile_off + l,
        rounding=rounding, seed_ref=sb_ref, e_acc=e_bwd, m_acc=m_bwd,
        row0=i * block_t, col0=j * block_k, n_cols=k)

    @pl.when(l == pl.num_programs(2) - 1)
    def _emit_dx():
        dx_ref[...] = dx_acc[...]

    # ---- dw: carry over i (middle), chunk = n1_grad, T order fixed ----
    sl = pl.dslice(l * block_n, block_n)
    prev = jnp.where(i == 0, jnp.zeros((block_k, block_n), jnp.float32),
                     dw_acc[:, sl])
    # SR coordinates mirror qmatmul_fused(x.T, g): dw element (k, n),
    # chunk step = the global T-chunk index
    dw_acc[:, sl] = fold_chunks(
        prev, x, g, _DW_DIMS, n1=n1_grad, tile_idx=i, rounding=rounding,
        seed_ref=sg_ref, e_acc=e_grad, m_acc=m_grad, row0=j * block_k,
        col0=col_off + l * block_n, n_cols=n)

    @pl.when(i == pl.num_programs(1) - 1)
    def _emit_dw():
        dw_ref[...] = dw_acc[:, sl]


def _pair_kernel_stats(*refs, e_r, m_r, qg, packed, e_bwd, m_bwd, e_grad,
                       m_grad, t, k, n, n1_bwd, n1_grad, rounding):
    """Swamping-telemetry variant of ``_pair_kernel``: the same two chunked
    accumulations plus wide (f32) shadow carries and a (2, N_STATS) stats
    reduction — row 0 for dx (the BWD accumulator), row 1 for dw (GRAD, the
    paper's critical long accumulation).  dx/dw outputs are bit-identical to
    the stats-off kernel."""
    if rounding == "sr":
        g_ref, x_ref, w_ref, sb_ref, sg_ref, dx_ref, dw_ref, stats_ref, \
            dx_acc, dw_acc, dxi_acc, dwi_acc, stats_acc = refs
    else:
        g_ref, x_ref, w_ref, dx_ref, dw_ref, stats_ref, \
            dx_acc, dw_acc, dxi_acc, dwi_acc, stats_acc = refs
        sb_ref = sg_ref = None
    j, i, l = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    block_t, block_k = dx_acc.shape
    block_n = dw_ref.shape[1]
    last_i = i == pl.num_programs(1) - 1
    last_l = l == pl.num_programs(2) - 1

    @pl.when((j == 0) & (i == 0) & (l == 0))
    def _init_stats():
        stats_acc[...] = jnp.zeros_like(stats_acc)

    g, x, w = _load_tiles(g_ref, x_ref, w_ref, qg=qg, packed=packed,
                          e_r=e_r, m_r=m_r)
    zero_row = jnp.zeros((N_STATS,), jnp.float32)

    def observer(ideal, mask, last_tile, row):
        """Per-chunk stats of one accumulator: advance its wide shadow
        carry and add the chunk's contribution to stats row ``row``."""
        def on_chunk(prev, new, partial, last):
            ideal[0] = ideal[0] + partial
            delta, step_max = stats_delta_row(new, prev, ideal[0], partial,
                                              mask, last_tile & last)
            rows = [zero_row, zero_row]
            rows[row] = delta
            maxes = [jnp.float32(0.0), jnp.float32(0.0)]
            maxes[row] = step_max
            stats_update(stats_acc, jnp.stack(rows), jnp.stack(maxes))
        return on_chunk

    # ---- dx: carry over l (innermost), chunk = n1_bwd ----
    @pl.when(l == 0)
    def _init_dx():
        dx_acc[...] = jnp.zeros_like(dx_acc)
        dxi_acc[...] = jnp.zeros_like(dxi_acc)

    mask_dx = ((i * block_t + jax.lax.broadcasted_iota(
        jnp.int32, (block_t, block_k), 0) < t)
        & (j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_t, block_k), 1) < k))
    dxi = [dxi_acc[...]]
    dx_acc[...] = fold_chunks(
        dx_acc[...], g, w, _DX_DIMS, n1=n1_bwd, tile_idx=l,
        rounding=rounding, seed_ref=sb_ref, e_acc=e_bwd, m_acc=m_bwd,
        row0=i * block_t, col0=j * block_k, n_cols=k,
        on_chunk=observer(dxi, mask_dx, last_l, 0))
    dxi_acc[...] = dxi[0]

    @pl.when(last_l)
    def _emit_dx():
        dx_ref[...] = dx_acc[...]

    # ---- dw: carry over i (middle), chunk = n1_grad ----
    sl = pl.dslice(l * block_n, block_n)
    zeros = jnp.zeros((block_k, block_n), jnp.float32)
    mask_dw = ((j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_k, block_n), 0) < k)
        & (l * block_n + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, block_n), 1) < n))
    dwi = [jnp.where(i == 0, zeros, dwi_acc[:, sl])]
    dw_acc[:, sl] = fold_chunks(
        jnp.where(i == 0, zeros, dw_acc[:, sl]), x, g, _DW_DIMS,
        n1=n1_grad, tile_idx=i, rounding=rounding, seed_ref=sg_ref,
        e_acc=e_grad, m_acc=m_grad, row0=j * block_k, col0=l * block_n,
        n_cols=n, on_chunk=observer(dwi, mask_dw, last_i, 1))
    dwi_acc[:, sl] = dwi[0]

    @pl.when(last_i)
    def _emit_dw():
        dw_ref[...] = dw_acc[:, sl]

    @pl.when((j == pl.num_programs(0) - 1) & last_i & last_l)
    def _emit_stats():
        stats_ref[...] = stats_acc[...]


@functools.partial(
    jax.jit,
    static_argnames=("e_r", "m_r", "qg", "packed", "e_bwd", "m_bwd",
                     "e_grad", "m_grad", "n1_grad", "block_k", "n1_bwd",
                     "collect_stats", "rounding", "n_total", "tile_off",
                     "col_off", "interpret"),
)
def _bwd_pair(g, xq, wq, dxc, sb, sg, *, e_r, m_r, qg, packed, e_bwd, m_bwd,
              e_grad, m_grad, n1_grad, block_k, n1_bwd, collect_stats=False,
              rounding="rne", n_total=0, tile_off=0, col_off=0,
              interpret=False):
    """The pair over one N range.  ``dxc`` (or None) is the running dx of
    the N segments before this one; ``n_total``/``tile_off``/``col_off``
    place the range on the global N axis (0 = the range is all of N)."""
    t, n = g.shape
    k = xq.shape[1]
    block_t, block_n = chunk_tile(n1_grad), chunk_tile(n1_bwd)
    rdt = jnp.int8 if packed else jnp.float32
    g2 = pad2d(g, block_t, block_n)
    x2 = pad2d(xq, block_t, block_k, dtype=rdt)
    w2 = pad2d(wq, block_k, block_n, dtype=rdt)
    tp, np_ = g2.shape
    kp = x2.shape[1]
    grid = (kp // block_k, tp // block_t, np_ // block_n)

    tk_spec = pl.BlockSpec((block_t, block_k), lambda j, i, l: (i, j))
    kn_spec = pl.BlockSpec((block_k, block_n), lambda j, i, l: (j, l))
    in_specs = [pl.BlockSpec((block_t, block_n), lambda j, i, l: (i, l)),
                tk_spec, kn_spec]                                 # g, x, w
    operands = [g2, x2, w2]
    if dxc is not None:
        in_specs.append(tk_spec)
        operands.append(pad2d(dxc, block_t, block_k))
    if rounding == "sr":
        in_specs += [pl.BlockSpec((1, 1), lambda j, i, l: (0, 0))] * 2
        operands += [sb, sg]
    out_specs = [tk_spec, kn_spec]                                # dx, dw
    out_shape = [jax.ShapeDtypeStruct((tp, kp), jnp.float32),
                 jax.ShapeDtypeStruct((kp, np_), jnp.float32)]
    scratch = [pltpu.VMEM((block_t, block_k), jnp.float32),      # dx carry
               pltpu.VMEM((block_k, np_), jnp.float32)]          # dw slab
    kw = dict(e_r=e_r, m_r=m_r, qg=qg, packed=packed, e_bwd=e_bwd,
              m_bwd=m_bwd, e_grad=e_grad, m_grad=m_grad, n1_bwd=n1_bwd,
              n1_grad=n1_grad, rounding=rounding, k=k)

    if collect_stats:
        dx, dw, stats = pl.pallas_call(
            functools.partial(_pair_kernel_stats, t=t, n=n, **kw),
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs + [
                pl.BlockSpec((2, N_STATS), lambda j, i, l: (0, 0))],
            out_shape=out_shape + [
                jax.ShapeDtypeStruct((2, N_STATS), jnp.float32)],
            scratch_shapes=scratch + [
                pltpu.VMEM((block_t, block_k), jnp.float32),  # dx ideal
                pltpu.VMEM((block_k, np_), jnp.float32),      # dw ideal slab
                pltpu.VMEM((2, N_STATS), jnp.float32),        # stats rows
            ],
            interpret=interpret,
        )(*operands)
        return dx[:t, :k], dw[:k, :n], stats

    dx, dw = pl.pallas_call(
        functools.partial(_pair_kernel, n=n_total or n,
                          resume=dxc is not None, tile_off=tile_off,
                          col_off=col_off, **kw),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
    )(*operands)
    return dx[:t, :k], dw[:k, :n]


@register_kernel("qmatmul_bwd_pair")
def qmatmul_bwd_pair(
    g: jnp.ndarray,
    xq: jnp.ndarray,
    wq: jnp.ndarray,
    *,
    repr_fmt=None,
    bwd_acc: tuple[int, int] = _WIDE,
    grad_acc: tuple[int, int] = _WIDE,
    block_t: int = 128,
    block_k: int = 128,
    block_n: int = 128,
    packed: bool = True,
    quantize_g: bool = True,
    collect_stats: bool = False,
    rounding: str = "rne",
    sr_seed_bwd=0,
    sr_seed_grad=0,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(dx, dw) of one dense layer in a single ``pallas_call``.

    * ``g`` — incoming gradient [T, N], f32, quantized to ``repr_fmt``
      in-kernel (once, shared by both contractions).
    * ``xq`` [T, K] / ``wq`` [K, N] — the forward's residuals, int8-packed
      codes when ``packed`` (unpacked in VMEM) else already-quantized f32.
    * ``bwd_acc`` / ``grad_acc`` — (e_acc, m_acc) accumulator formats.
    * ``block_n`` is the BWD chunk length (numerics), ``block_t`` the GRAD
      chunk length (numerics); only ``block_k`` is schedule-only.  Each
      chunk length must divide 128 or be a multiple of it: the kernel
      tiles T and N by ``chunk_tile`` and folds each tile's chunks in
      order, keeping one carry rounding per chunk.
    * ``collect_stats=True`` returns ``(dx, dw, stats)`` with ``stats`` a
      (2, N_STATS) raw telemetry block — row 0 the dx (BWD) accumulator,
      row 1 the dw (GRAD) accumulator; dx/dw stay bit-identical.  Roughly
      doubles the VMEM working set (wide shadow carries), which is why the
      telemetry probe, not the train step, is the caller.
    * ``rounding="sr"`` stochastically rounds BOTH carries; the two
      accumulators take separate seeds (``sr_seed_bwd`` / ``sr_seed_grad``)
      so dx matches ``qmatmul_fused(g, w.T, sr_seed=sr_seed_bwd)`` and dw
      matches ``qmatmul_fused(x.T, g, sr_seed=sr_seed_grad)`` bitwise.
    """
    if g.ndim != 2 or xq.ndim != 2 or wq.ndim != 2:
        raise ValueError("2D operands required")
    if xq.shape[0] != g.shape[0] or wq.shape[1] != g.shape[1] \
            or wq.shape[0] != xq.shape[1]:
        raise ValueError(
            f"bad shapes g{g.shape} x{xq.shape} w{wq.shape}")
    fmt = fmt_tuple(repr_fmt)
    if fmt is None:
        if packed:
            raise ValueError("packed residuals need repr_fmt to decode")
        e_r, m_r = _WIDE
        quantize_g = False
    else:
        e_r, m_r = fmt
    if packed and (xq.dtype != jnp.int8 or wq.dtype != jnp.int8):
        raise ValueError(
            f"packed=True expects int8 codes, got {xq.dtype}/{wq.dtype} "
            "(f32 carriers would be silently value-truncated)")
    if rounding not in ROUNDINGS:
        raise ValueError(f"rounding must be one of {ROUNDINGS}, "
                         f"got {rounding!r}")
    (e_b, m_b), (e_g, m_g) = bwd_acc, grad_acc
    return _bwd_pair(
        g, xq, wq, None, as_sr_seed(sr_seed_bwd), as_sr_seed(sr_seed_grad),
        e_r=int(e_r), m_r=int(m_r), qg=quantize_g, packed=packed,
        e_bwd=int(e_b), m_bwd=int(m_b), e_grad=int(e_g), m_grad=int(m_g),
        n1_grad=int(block_t), block_k=block_k, n1_bwd=int(block_n),
        collect_stats=collect_stats, rounding=rounding,
        interpret=resolve_interpret(interpret),
    )


@register_kernel("qmatmul_bwd_pair_nsplit")
def qmatmul_bwd_pair_nsplit(
    g: jnp.ndarray,
    xq: jnp.ndarray,
    wq: jnp.ndarray,
    *,
    n_split: int,
    repr_fmt=None,
    bwd_acc: tuple[int, int] = _WIDE,
    grad_acc: tuple[int, int] = _WIDE,
    block_t: int = 128,
    block_k: int = 128,
    block_n: int = 128,
    packed: bool = True,
    quantize_g: bool = True,
    rounding: str = "rne",
    sr_seed_bwd=0,
    sr_seed_grad=0,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The backward pair split into ``n_split`` N segments (wide-N layers
    whose (block_k, N_padded) dw carry slab busts the VMEM budget,
    lm_head-scale fan-outs) — ROADMAP "bwd-pair VMEM scaling".

    Each segment is one pallas_call over its N slice: dw columns are emitted
    per segment (the dw accumulation runs over T, untouched by the split)
    and the dx chunked accumulation CONTINUES across segments via an
    explicit carry tensor, in the same N order and block_n rounding cadence
    as the unsplit kernel — bit-identical results (pinned in
    tests/test_fused.py).  Against the two-call fallback this keeps the
    pair's traffic shape: g and w are still read once in total (each segment
    reads only its N slice) where the fallback re-reads and re-quantizes g
    for each GEMM; the price is one x re-read plus one dx carry round-trip
    per extra segment.
    """
    if n_split < 2:
        raise ValueError("n_split >= 2; use qmatmul_bwd_pair for one pass")
    t, n = g.shape
    k = xq.shape[1]
    fmt = fmt_tuple(repr_fmt)
    if fmt is None:
        if packed:
            raise ValueError("packed residuals need repr_fmt to decode")
        e_r, m_r = _WIDE
        quantize_g = False
    else:
        e_r, m_r = fmt
    if rounding not in ROUNDINGS:
        raise ValueError(f"rounding must be one of {ROUNDINGS}, "
                         f"got {rounding!r}")
    (e_b, m_b), (e_g, m_g) = bwd_acc, grad_acc
    sb, sg = as_sr_seed(sr_seed_bwd), as_sr_seed(sr_seed_grad)
    # tile-aligned segment edges: the global chunk sequence over N is the
    # unsplit kernel's (padding chunks are carry no-ops: q(c + 0) == c)
    tile_n = chunk_tile(block_n)
    seg = pair_segment_width(n, n_split, block_n)
    dx = jnp.zeros((t, k), jnp.float32)
    dws = []
    for lo in range(0, n, seg):
        hi = min(lo + seg, n)
        dx, dw_s = _bwd_pair(
            g[:, lo:hi], xq, wq[:, lo:hi], dx, sb, sg,
            e_r=int(e_r), m_r=int(m_r), qg=quantize_g, packed=packed,
            e_bwd=int(e_b), m_bwd=int(m_b), e_grad=int(e_g),
            m_grad=int(m_g), n1_grad=int(block_t), block_k=block_k,
            n1_bwd=int(block_n), rounding=rounding, n_total=n,
            tile_off=lo // tile_n, col_off=lo,
            interpret=resolve_interpret(interpret))
        dws.append(dw_s)
    return dx, jnp.concatenate(dws, axis=1)
