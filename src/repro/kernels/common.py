"""Shared helpers for the Pallas kernels."""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "quantize_block",
    "quantize_block_sr",
    "quantize_carry",
    "carry_update",
    "threefry2x32",
    "sr_random_bits",
    "ROUNDINGS",
    "LANES",
    "chunk_tile",
    "fold_chunks",
    "resolve_interpret",
    "pad2d",
    "count_pallas_calls",
    "count_pallas_executions",
    "N_STATS",
    "STAT_COUNT",
    "STAT_SUM_Q",
    "STAT_SUMSQ_Q",
    "STAT_SUM_I",
    "STAT_SUMSQ_I",
    "STAT_MAX_ABS",
    "STAT_SWAMPED",
    "STAT_ADDS",
    "STAT_SUM_ERR",
    "STAT_SUMSQ_ERR",
    "stats_delta_row",
    "stats_update",
]

def resolve_interpret(interpret: bool | None) -> bool:
    """``interpret=None`` means: interpret only on the CPU backend, asked at
    call time.  Pallas kernels target the TPU; on the CPU the interpreter
    runs the kernel body with the same block decomposition.  A TPU that
    failed to start therefore makes the kernels interpret visibly on a CPU
    backend rather than behind an import-time constant."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return bool(interpret)


# Lane width of a TPU vector register: the minor dimension of a kernel's
# block shape must be a multiple of it (or span the whole array).
LANES = 128


def chunk_tile(n1: int) -> int:
    """The contraction tile that holds a whole number of n1-chunks: the
    lane width when n1 divides it, else n1 itself when it is a multiple of
    the lane width.  The chunk length n1 is numerics (the paper's chunk);
    the tile is the block the TPU can load.  Any other n1 is refused."""
    n1 = int(n1)
    if n1 > 0 and LANES % n1 == 0:
        return LANES
    if n1 > 0 and n1 % LANES == 0:
        return n1
    raise ValueError(
        f"chunk length n1={n1} must divide {LANES} or be a multiple of it: "
        f"the TPU loads contraction tiles in multiples of {LANES} lanes")


def fold_chunks(acc, a, b, dims=(((1,), (0,)), ((), ())), *, n1, tile_idx,
                rounding, seed_ref, e_acc, m_acc, row0, col0, n_cols,
                on_chunk=None):
    """Fold one contraction tile into a carry, one n1-chunk at a time: each
    chunk's product is contracted in ideal f32 (intra-chunk) and the carry
    is rounded after it (inter-chunk) under the chunk's GLOBAL index
    ``tile_idx * (tile // n1) + s``.  A tile of several chunks thus keeps
    the carry order and the SR dither stream of one chunk per grid step.
    ``on_chunk(prev, new, partial, last)`` observes each update (the
    telemetry epilogues); ``last`` marks the tile's final chunk."""
    (ca,), (cb,) = dims[0]
    per = a.shape[ca] // n1
    for s in range(per):
        lo, hi = s * n1, (s + 1) * n1
        partial = jax.lax.dot_general(
            jax.lax.slice_in_dim(a, lo, hi, axis=ca),
            jax.lax.slice_in_dim(b, lo, hi, axis=cb),
            dims, preferred_element_type=jnp.float32)
        prev = acc
        acc = carry_update(
            prev, partial, e_acc=e_acc, m_acc=m_acc, rounding=rounding,
            seed_ref=seed_ref, step=tile_idx * per + s, row0=row0,
            col0=col0, n_cols=n_cols)
        if on_chunk is not None:
            on_chunk(prev, acc, partial, s == per - 1)
    return acc


def pad2d(x: jnp.ndarray, rows: int, cols: int,
          dtype: jnp.dtype = jnp.float32) -> jnp.ndarray:
    """Zero-pad a 2D array up to (rows, cols) multiples, as ``dtype``.

    Zero padding composes exactly with the (1, e, m) quantizer (q(0) = 0) and
    with the chunked carry update (adding an all-zero chunk product leaves the
    already-quantized carry unchanged), so padded and unpadded GEMMs agree
    bit-for-bit on the valid region.  For int8-packed operands the same holds:
    code 0 decodes to +0.0.
    """
    r, c = x.shape
    rp = -(-r // rows) * rows
    cp = -(-c // cols) * cols
    return jnp.pad(x.astype(dtype), ((0, rp - r), (0, cp - c)))


def count_pallas_calls(fn, *args, **kwargs) -> int:
    """Number of ``pallas_call`` equations in ``jax.make_jaxpr(fn)(*args)``,
    including nested sub-jaxprs (custom_vjp bodies, scans, cond branches).

    This is the unit the fused-GEMM work is accounted in: one pallas_call ==
    one HBM round-trip over its operands.
    """
    import functools

    jaxpr = jax.make_jaxpr(functools.partial(fn, **kwargs))(*args)
    return _count_eqns(jaxpr.jaxpr)


def count_pallas_executions(fn, *args, **kwargs) -> int:
    """Like ``count_pallas_calls`` but weights equations inside ``lax.scan``
    bodies by the scan's trip count, so a rolled layer stack reports the
    passes one EXECUTION performs (a scanned stack's body appears once in
    the jaxpr however many layers it runs)."""
    import functools

    jaxpr = jax.make_jaxpr(functools.partial(fn, **kwargs))(*args)
    return _count_eqns(jaxpr.jaxpr, weighted=True)


def _count_eqns(jaxpr, weighted: bool = False) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            n += 1
        mult = 1
        if weighted and eqn.primitive.name == "scan":
            mult = int(eqn.params.get("length", 1))
        for v in eqn.params.values():
            n += mult * _count_in_param(v, weighted)
    return n


def _count_in_param(v, weighted: bool = False) -> int:
    if hasattr(v, "jaxpr"):  # ClosedJaxpr
        return _count_eqns(v.jaxpr, weighted)
    if hasattr(v, "eqns"):  # raw Jaxpr
        return _count_eqns(v, weighted)
    if isinstance(v, (list, tuple)):
        return sum(_count_in_param(x, weighted) for x in v)
    return 0


# --------------------------------------------------------------------------
# swamping-telemetry stats epilogue (repro.telemetry)
# --------------------------------------------------------------------------
#
# Raw in-kernel stats vector: one f32 row of N_STATS slots per monitored
# accumulator, reduced over the whole GEMM grid in a VMEM scratch and
# emitted as a small extra output when ``collect_stats=True``.  The layout
# is the kernel<->telemetry contract; ``repro.telemetry.stats.EnsembleStats``
# is the only consumer.  Counters are f32 (exact up to 2^24 events; beyond
# that the swamp *rate* stays accurate, which is all the controller reads).

N_STATS = 10
(
    STAT_COUNT,     # valid output elements (the ensemble size)
    STAT_SUM_Q,     # sum of reduced-precision outputs
    STAT_SUMSQ_Q,   # sum of squared reduced-precision outputs
    STAT_SUM_I,     # sum of ideal (f32-accumulated) outputs
    STAT_SUMSQ_I,   # sum of squared ideal outputs
    STAT_MAX_ABS,   # max |carry| over all chunk updates (exponent proxy)
    STAT_SWAMPED,   # chunk-carry adds fully absorbed: q(c + p) == c, p != 0
    STAT_ADDS,      # chunk-carry adds with a non-zero addend
    STAT_SUM_ERR,   # sum of (q - ideal) over final outputs (rounding bias)
    STAT_SUMSQ_ERR, # sum of (q - ideal)^2 over final outputs (rounding MSE)
) = range(N_STATS)


def stats_delta_row(new, prev, ideal, partial, mask, emit_out):
    """Per-grid-step stats contribution for one chunk-carry update.

    ``new``/``prev`` are the carry tile after/before ``quantize(prev +
    partial)``, ``ideal`` the wide (f32) carry, ``mask`` the valid-region
    mask of the tile, ``emit_out`` a traced bool — True on the tile's final
    chunk, when the carry IS the output and its ensemble moments are taken.
    Returns ``(delta, step_max)``: an (N_STATS,) additive contribution
    (zero in the MAX_ABS slot) and the step's max |carry| for the max-merge.
    """
    one = jnp.float32(1.0)
    zero = jnp.float32(0.0)
    nz = (partial != 0.0) & mask
    swamped = jnp.sum(jnp.where((new == prev) & nz, one, zero))
    adds = jnp.sum(jnp.where(nz, one, zero))
    om = mask & emit_out
    q = jnp.where(om, new, 0.0)
    w = jnp.where(om, ideal, 0.0)
    err = q - w
    cnt = jnp.sum(jnp.where(om, one, zero))
    delta = jnp.stack([cnt, jnp.sum(q), jnp.sum(q * q),
                       jnp.sum(w), jnp.sum(w * w), zero, swamped, adds,
                       jnp.sum(err), jnp.sum(err * err)])
    step_max = jnp.max(jnp.where(mask, jnp.abs(new), 0.0))
    return delta, step_max


def stats_update(stats_acc, deltas, maxes):
    """Accumulate per-step contributions into the (R, N_STATS) stats scratch:
    every slot adds, except MAX_ABS which max-merges."""
    cur = stats_acc[...]
    col = jax.lax.broadcasted_iota(jnp.int32, cur.shape, 1)
    stats_acc[...] = jnp.where(col == STAT_MAX_ABS,
                               jnp.maximum(cur, maxes[:, None]),
                               cur + deltas)


def quantize_block(x: jnp.ndarray, e: int, m: int) -> jnp.ndarray:
    """(1, e, m) round-to-nearest-even quantization of a float32 block.

    Same semantics as repro.quant.qnum.quantize but written against
    lax.bitcast_convert_type so it lowers inside a Pallas kernel body.
    Saturating (no inf), flush-to-zero subnormals, NaN propagated.
    """
    if m >= 23 and e >= 8:
        return x
    max_value = jnp.float32(2.0 ** (2 ** (e - 1) - 1) * (2.0 - 2.0 ** (-m)))
    min_normal = jnp.float32(2.0 ** -(2 ** (e - 1) - 1))

    y = jnp.abs(x)
    if m < 23:
        xi = jax.lax.bitcast_convert_type(y, jnp.uint32)
        shift = jnp.uint32(23 - m)
        lsb = (xi >> shift) & jnp.uint32(1)
        round_bias = (jnp.uint32(1) << (shift - jnp.uint32(1))) - jnp.uint32(1) + lsb
        xi = xi + round_bias
        xi = xi & ~((jnp.uint32(1) << shift) - jnp.uint32(1))
        y = jax.lax.bitcast_convert_type(xi, jnp.float32)

    y = jnp.where(jnp.isinf(x), max_value, y)
    y = jnp.minimum(y, max_value)
    y = jnp.where(y < min_normal, jnp.float32(0.0), y)
    y = jnp.where(jnp.signbit(x), -y, y)
    return jnp.where(jnp.isnan(x), x, y)


# --------------------------------------------------------------------------
# stochastic-rounding carry (rounding="sr")
# --------------------------------------------------------------------------
#
# Counter-based Threefry-2x32 written in plain uint32 ops so it lowers both
# on TPU and in interpret mode (the pltpu.prng_* primitives have no CPU
# lowering).  The carry noise is a pure function of (seed, chunk step,
# logical output element), never of tile shapes or grid schedule — which is
# what makes seeded SR bitwise-reproducible across the fused, bwd-pair,
# segmented and stats-epilogue kernel variants.

ROUNDINGS = ("rne", "sr")


def _rotl32(x: jnp.ndarray, d: int) -> jnp.ndarray:
    return (x << jnp.uint32(d)) | (x >> jnp.uint32(32 - d))


def threefry2x32(key0, key1, ctr0, ctr1):
    """Standard 20-round Threefry-2x32 block: (key, counter) -> two uint32
    words.  Inputs broadcast; all arithmetic is mod-2^32 uint32."""
    rots = ((13, 15, 26, 6), (17, 29, 16, 24))
    ks = (jnp.uint32(key0), jnp.uint32(key1),
          jnp.uint32(key0) ^ jnp.uint32(key1) ^ jnp.uint32(0x1BD11BDA))
    x0 = jnp.uint32(ctr0) + ks[0]
    x1 = jnp.uint32(ctr1) + ks[1]
    for g in range(5):
        for d in rots[g % 2]:
            x0 = x0 + x1
            x1 = _rotl32(x1, d) ^ x0
        x0 = x0 + ks[(g + 1) % 3]
        x1 = x1 + ks[(g + 2) % 3] + jnp.uint32(g + 1)
    return x0, x1


def sr_random_bits(seed, step, row_ids, col_ids, n_cols: int) -> jnp.ndarray:
    """Deterministic uint32 dither for one carry-update tile.

    The Threefry counter pairs the element's flat LOGICAL output index
    (``row * n_cols + col`` over the unpadded output) with the chunk-step
    index, and the key is the caller's seed.  Padded elements may alias a
    logical index, which is harmless: the dither is consumed elementwise
    and the padded region is discarded.
    """
    flat = row_ids.astype(jnp.uint32) * jnp.uint32(n_cols) + \
        col_ids.astype(jnp.uint32)
    seed = jnp.asarray(seed).astype(jnp.uint32)
    step = jnp.asarray(step).astype(jnp.uint32)
    out, _ = threefry2x32(seed, seed ^ jnp.uint32(0x9E3779B9), flat, step)
    return out


def quantize_block_sr(x: jnp.ndarray, e: int, m: int,
                      rbits: jnp.ndarray) -> jnp.ndarray:
    """(1, e, m) stochastic-rounding quantization of a float32 block.

    Adds ``rbits & (ulp_bits - 1)`` — a uniform dither in [0, ulp) on the
    magnitude's bit pattern — then truncates the mantissa, which rounds up
    with probability exactly equal to the discarded fraction: conditionally
    unbiased per rounding event.  Saturation, flush-to-zero and NaN
    semantics match ``quantize_block``; only the mantissa rounding rule
    differs (and exact formats degenerate to identity, dither unused).
    """
    if m >= 23 and e >= 8:
        return x
    max_value = jnp.float32(2.0 ** (2 ** (e - 1) - 1) * (2.0 - 2.0 ** (-m)))
    min_normal = jnp.float32(2.0 ** -(2 ** (e - 1) - 1))

    y = jnp.abs(x)
    if m < 23:
        xi = jax.lax.bitcast_convert_type(y, jnp.uint32)
        shift = jnp.uint32(23 - m)
        low = (jnp.uint32(1) << shift) - jnp.uint32(1)
        xi = xi + (rbits & low)
        xi = xi & ~low
        y = jax.lax.bitcast_convert_type(xi, jnp.float32)

    y = jnp.where(jnp.isinf(x), max_value, y)
    y = jnp.minimum(y, max_value)
    y = jnp.where(y < min_normal, jnp.float32(0.0), y)
    y = jnp.where(jnp.signbit(x), -y, y)
    return jnp.where(jnp.isnan(x), x, y)


def quantize_carry(x: jnp.ndarray, e: int, m: int, rounding: str,
                   rbits=None) -> jnp.ndarray:
    """Carry-update quantizer dispatch: RNE (default, bit-identical to the
    historical kernels) or SR with caller-supplied dither bits."""
    if rounding == "sr":
        return quantize_block_sr(x, e, m, rbits)
    return quantize_block(x, e, m)


def carry_update(prev, partial, *, e_acc, m_acc, rounding, seed_ref,
                 step, row0, col0, n_cols):
    """One inter-chunk carry update for a kernel tile.  ``rounding="rne"``
    is the historical bit-exact path; ``"sr"`` draws the per-element dither
    from the seed and the element's LOGICAL coordinates (global row/col of
    the tile origin, chunk-step index), so the bits are invariant to tile
    shape and grid schedule — the cross-variant determinism contract."""
    if rounding != "sr":
        return quantize_block(prev + partial, e_acc, m_acc)
    shape = prev.shape
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    cols = col0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    rbits = sr_random_bits(seed_ref[0, 0], step, rows, cols, n_cols)
    return quantize_block_sr(prev + partial, e_acc, m_acc, rbits)
