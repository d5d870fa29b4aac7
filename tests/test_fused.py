"""Fused quantize+GEMM pipeline: bit-exact equivalence against the unfused
quantize_pallas -> qmatmul_pallas composition and the pure-jnp oracle, the
int8-packed residual/operand epilogues, the one-pass backward pair, and the
pipeline accounting (ONE pallas_call forward + ONE backward per qdot).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.policy import GEMMPrecision
from repro.kernels.bwd_pair import qmatmul_bwd_pair
from repro.kernels.common import count_pallas_calls
from repro.kernels.fused import qmatmul_fused
from repro.kernels.ops import (QDotConfig, _encode_seed, _qdot2d_fwd, qdot,
                               qdot_packed)
from repro.kernels.qmatmul import qmatmul_pallas
from repro.kernels.quantize import quantize_pallas
from repro.kernels.ref import ref_qmatmul
from repro.quant.formats import FP8_152, FPFormat
from repro.quant.qnum import quantize
from repro.quant.qtensor import QTensor, pack_block, unpack_block

# ragged/padded shapes exercise every block-edge case of the M/N/K padding
SHAPES = [(128, 128, 128), (64, 256, 32), (100, 300, 50), (8, 8, 8),
          (1, 512, 1), (130, 257, 61)]


def _rand(m, k, n, seed):
    rng = np.random.RandomState(seed)
    a = jnp.asarray(rng.standard_normal((m, k)).astype(np.float32))
    b = jnp.asarray(rng.standard_normal((k, n)).astype(np.float32))
    return a, b


# ------------------------- kernel-level equivalence -------------------------


@pytest.mark.parametrize("m,k,n", SHAPES)
@pytest.mark.parametrize("m_acc,block_k", [(5, 64), (9, 128), (6, 32),
                                           (8, 256)])
def test_fused_matches_unfused_composition_bitexact(m, k, n, m_acc, block_k):
    a, b = _rand(m, k, n, hash((m, k, n, m_acc)) % 2**32)
    got = np.asarray(qmatmul_fused(
        a, b, repr_fmt=FP8_152, e_acc=6, m_acc=m_acc, block_k=block_k))
    want = np.asarray(qmatmul_pallas(
        quantize_pallas(a, e=5, m=2), quantize_pallas(b, e=5, m=2),
        e_acc=6, m_acc=m_acc, block_k=block_k))
    np.testing.assert_array_equal(got, want)


def test_chunk_length_off_the_lanes_is_refused():
    # n1 = 48 neither divides the 128-lane K tile nor is a multiple of it:
    # refused on every backend, before any kernel is built
    a, b = _rand(8, 96, 16, 5)
    with pytest.raises(ValueError, match="must divide 128"):
        qmatmul_fused(a, b, repr_fmt=FP8_152, e_acc=6, m_acc=5, block_k=48)
    g = jnp.zeros((8, 16), jnp.float32)
    with pytest.raises(ValueError, match="must divide 128"):
        qmatmul_bwd_pair(g, a, b, repr_fmt=FP8_152, packed=False,
                         block_t=48)


@pytest.mark.parametrize("m,k,n", SHAPES)
def test_fused_matches_ref_oracle_bitexact(m, k, n):
    a, b = _rand(m, k, n, hash((m, k, n)) % 2**32)
    got = np.asarray(qmatmul_fused(
        a, b, repr_fmt=FP8_152, e_acc=6, m_acc=7, block_k=64))
    want = np.asarray(ref_qmatmul(
        quantize(a, FP8_152), quantize(b, FP8_152),
        e_acc=6, m_acc=7, block_k=64))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("m,k,n", [(96, 384, 64), (100, 300, 50)])
def test_fused_wide_degenerate_path(m, k, n):
    # no repr quantization + (1,8,23) carry: the fused kernel IS the plain
    # tiled matmul, bit-identical to qmatmul_pallas
    a, b = _rand(m, k, n, 7)
    got = np.asarray(qmatmul_fused(a, b))
    np.testing.assert_array_equal(got, np.asarray(qmatmul_pallas(a, b)))
    np.testing.assert_allclose(got, np.asarray(a) @ np.asarray(b),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("blocks", [(128, 128), (256, 128), (128, 256),
                                    (256, 256)])
def test_fused_mn_blocking_is_schedule_only(blocks):
    # block_m/block_n must not change numerics: the per-output-element
    # reduction order over K is fixed by block_k alone
    bm, bn = blocks
    a, b = _rand(300, 256, 200, 11)
    base = np.asarray(qmatmul_fused(
        a, b, repr_fmt=FP8_152, e_acc=6, m_acc=6, block_k=64))
    got = np.asarray(qmatmul_fused(
        a, b, repr_fmt=FP8_152, e_acc=6, m_acc=6,
        block_m=bm, block_n=bn, block_k=64))
    np.testing.assert_array_equal(got, base)


def test_fused_emits_quantized_residuals():
    a, b = _rand(100, 300, 50, 13)
    y, aq, bq = qmatmul_fused(a, b, repr_fmt=FP8_152, e_acc=6, m_acc=7,
                              block_k=64, return_quantized=True)
    np.testing.assert_array_equal(
        np.asarray(aq), np.asarray(quantize_pallas(a, e=5, m=2)))
    np.testing.assert_array_equal(
        np.asarray(bq), np.asarray(quantize_pallas(b, e=5, m=2)))
    np.testing.assert_array_equal(
        np.asarray(y),
        np.asarray(qmatmul_fused(a, b, repr_fmt=FP8_152, e_acc=6, m_acc=7,
                                 block_k=64)))


def test_fused_packed_residual_epilogue():
    # pack_residuals: the same epilogue, int8 codes — decoded, bit-identical
    # to the f32-carrier emission; 1 byte per element on the way to HBM
    a, b = _rand(100, 300, 50, 13)
    y, aq, bq = qmatmul_fused(a, b, repr_fmt=FP8_152, e_acc=6, m_acc=7,
                              block_k=64, return_quantized=True)
    y2, aqp, bqp = qmatmul_fused(a, b, repr_fmt=FP8_152, e_acc=6, m_acc=7,
                                 block_k=64, return_quantized=True,
                                 pack_residuals=True)
    assert aqp.dtype == jnp.int8 and bqp.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(y2), np.asarray(y))
    np.testing.assert_array_equal(
        np.asarray(unpack_block(aqp, 5, 2)), np.asarray(aq))
    np.testing.assert_array_equal(
        np.asarray(unpack_block(bqp, 5, 2)), np.asarray(bq))


def test_fused_consumes_packed_operands_in_kernel():
    # int8 codes in, same GEMM out: the in-VMEM unpack is bit-exact
    a, b = _rand(130, 257, 61, 15)
    aq = quantize(a, FP8_152)
    bq = quantize(b, FP8_152)
    want = np.asarray(qmatmul_fused(aq, bq, repr_fmt=FP8_152, e_acc=6,
                                    m_acc=7, block_k=64))
    got = np.asarray(qmatmul_fused(
        pack_block(aq, 5, 2), pack_block(bq, 5, 2), repr_fmt=FP8_152,
        e_acc=6, m_acc=7, block_k=64, a_packed=True, b_packed=True))
    np.testing.assert_array_equal(got, want)


def test_fused_out_fmt_epilogue_matches_posthoc_quantization():
    # consumer-format fold: epilogue rounding == a separate output-path
    # quantization pass, so that pass can be (and is) dropped
    a, b = _rand(100, 300, 50, 21)
    base = qmatmul_fused(a, b, repr_fmt=FP8_152, e_acc=6, m_acc=7, block_k=64)
    got = np.asarray(qmatmul_fused(a, b, repr_fmt=FP8_152, e_acc=6, m_acc=7,
                                   block_k=64, out_fmt=FP8_152))
    np.testing.assert_array_equal(got, np.asarray(quantize(base, FP8_152)))
    # ... and the consumer may skip its own input quantization bit-exactly
    w2 = jnp.asarray(np.random.RandomState(5).standard_normal(
        (got.shape[1], 30)).astype(np.float32))
    on = np.asarray(qmatmul_fused(jnp.asarray(got), w2, repr_fmt=FP8_152,
                                  e_acc=6, m_acc=5, block_k=64))
    off = np.asarray(qmatmul_fused(jnp.asarray(got), w2, repr_fmt=FP8_152,
                                   e_acc=6, m_acc=5, block_k=64,
                                   quantize_a=False))
    np.testing.assert_array_equal(on, off)
    # pack_out: the output itself leaves the kernel as int8 codes
    codes = qmatmul_fused(a, b, repr_fmt=FP8_152, e_acc=6, m_acc=7,
                          block_k=64, out_fmt=FP8_152, pack_out=True)
    assert codes.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(unpack_block(codes, 5, 2)), got)


# ------------------------- one-pass backward pair ---------------------------


@pytest.mark.parametrize("t,k,n", [(64, 128, 32), (100, 300, 50),
                                   (130, 257, 61), (1, 512, 1)])
def test_bwd_pair_matches_separate_gemms_bitexact(t, k, n):
    rng = np.random.RandomState(t * 7 + n)
    g = jnp.asarray(rng.standard_normal((t, n)).astype(np.float32))
    x = jnp.asarray(rng.standard_normal((t, k)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((k, n)).astype(np.float32))
    xq, wq = quantize(x, FP8_152), quantize(w, FP8_152)
    dx_ref = qmatmul_fused(g, wq.T, repr_fmt=FP8_152, e_acc=6, m_acc=5,
                           block_k=64, quantize_a=True, quantize_b=False)
    dw_ref = qmatmul_fused(xq.T, g, repr_fmt=FP8_152, e_acc=6, m_acc=8,
                           block_k=64, quantize_a=False, quantize_b=True)
    dx, dw = qmatmul_bwd_pair(
        g, pack_block(xq, 5, 2), pack_block(wq, 5, 2), repr_fmt=FP8_152,
        bwd_acc=(6, 5), grad_acc=(6, 8), block_t=64, block_n=64, packed=True)
    np.testing.assert_array_equal(np.asarray(dx), np.asarray(dx_ref))
    np.testing.assert_array_equal(np.asarray(dw), np.asarray(dw_ref))


def test_bwd_pair_is_one_pallas_call():
    rng = np.random.RandomState(9)
    g = jnp.asarray(rng.standard_normal((64, 32)).astype(np.float32))
    xq = pack_block(quantize(
        jnp.asarray(rng.standard_normal((64, 48)).astype(np.float32)),
        FP8_152), 5, 2)
    wq = pack_block(quantize(
        jnp.asarray(rng.standard_normal((48, 32)).astype(np.float32)),
        FP8_152), 5, 2)
    n = count_pallas_calls(
        lambda g: qmatmul_bwd_pair(g, xq, wq, repr_fmt=FP8_152,
                                   bwd_acc=(6, 5), grad_acc=(6, 8),
                                   block_t=64, block_n=64), g)
    assert n == 1


@pytest.mark.parametrize("n_split", [2, 3, 5])
def test_bwd_pair_nsplit_matches_unsplit_bitexact(n_split):
    # ROADMAP "bwd-pair VMEM scaling": the N-split pair must be the SAME
    # function as the one-pass kernel — dx carry chained across segments in
    # the unsplit chunk order, dw emitted per segment slice
    from repro.kernels.bwd_pair import qmatmul_bwd_pair_nsplit

    rng = np.random.RandomState(61)
    t, k, n = 100, 96, 300
    g = jnp.asarray(rng.standard_normal((t, n)).astype(np.float32))
    xq = quantize(jnp.asarray(rng.standard_normal((t, k)).astype(np.float32)),
                  FP8_152)
    wq = quantize(jnp.asarray(rng.standard_normal((k, n)).astype(np.float32)),
                  FP8_152)
    kw = dict(repr_fmt=FP8_152, bwd_acc=(6, 5), grad_acc=(6, 8),
              block_t=64, block_n=64, packed=True)
    dx0, dw0 = qmatmul_bwd_pair(g, pack_block(xq, 5, 2), pack_block(wq, 5, 2),
                                **kw)
    dx1, dw1 = qmatmul_bwd_pair_nsplit(
        g, pack_block(xq, 5, 2), pack_block(wq, 5, 2), n_split=n_split, **kw)
    np.testing.assert_array_equal(np.asarray(dx1), np.asarray(dx0))
    np.testing.assert_array_equal(np.asarray(dw1), np.asarray(dw0))


def test_qdot_wide_n_takes_nsplit_path_not_fallback(monkeypatch):
    # a VMEM budget too small for the unsplit slab but big enough for
    # segments: pair_n_segments must route qdot's backward onto the N-split
    # pair, and the grads must stay bit-identical to the unfused oracle
    from repro.kernels.ops import pair_n_segments

    t, k, n = 32, 64, 1024
    cfg = _cfg()
    assert pair_n_segments(cfg, t, k, n) == 1
    monkeypatch.setenv("REPRO_VMEM_BUDGET", str(450_000))
    segs = pair_n_segments(cfg, t, k, n)
    assert segs > 1, "budget should force the N-split path"

    x, w = _rand(t, k, n, 67)
    y_f = qdot(x, w, cfg)
    y_u = qdot(x, w, _cfg(fused=False))
    np.testing.assert_array_equal(np.asarray(y_f), np.asarray(y_u))

    def loss(c):
        return lambda x, w: jnp.sum(jnp.sin(qdot(x, w, c)))

    g_f = jax.grad(loss(_cfg()), argnums=(0, 1))(x, w)
    g_u = jax.grad(loss(_cfg(fused=False)), argnums=(0, 1))(x, w)
    for a, b in zip(g_f, g_u):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and the pass count is the segment count, not the 2-call fallback's
    # quantize-twice structure: segs backward passes + 1 forward
    passes = _train_passes(_cfg(), x, w)
    assert passes <= 1 + segs


def test_pair_n_segments_boundaries():
    from repro.kernels.ops import pair_n_segments

    cfg = _cfg()
    # fits outright
    assert pair_n_segments(cfg, 64, 64, 128) == 1
    # unfused configs never take the pair path
    assert pair_n_segments(_cfg(fused=False), 64, 64, 128) == 0
    # an absurdly small budget: even single-chunk segments bust -> fallback
    assert pair_n_segments(cfg, 64, 64, 4096, vmem=1024) == 0


def test_fused_requantization_is_free():
    # quantizer idempotence: feeding already-quantized operands with
    # quantization ON equals feeding them with quantization OFF — the
    # backward pass relies on this to skip residual re-quantization
    a, b = _rand(64, 128, 32, 17)
    aq, bq = quantize(a, FP8_152), quantize(b, FP8_152)
    on = np.asarray(qmatmul_fused(aq, bq, repr_fmt=FP8_152,
                                  e_acc=6, m_acc=5, block_k=64))
    off = np.asarray(qmatmul_fused(aq, bq, repr_fmt=FP8_152, e_acc=6,
                                   m_acc=5, block_k=64,
                                   quantize_a=False, quantize_b=False))
    np.testing.assert_array_equal(on, off)


# --------------------------- qdot pipeline shape ----------------------------


def _cfg(fused=True, repr_fmt=FP8_152, pack=True, out_fmt=None):
    p = GEMMPrecision(m_acc=9, e_acc=6, chunk=64)
    return QDotConfig(fwd=p, bwd=p, grad=p, repr_fmt=repr_fmt, fused=fused,
                      pack_residuals=pack, out_fmt=out_fmt)


def _train_passes(cfg, x, w):
    return count_pallas_calls(
        lambda x, w: jax.value_and_grad(
            lambda x, w: jnp.sum(qdot(x, w, cfg)), argnums=(0, 1))(x, w),
        x, w)


def test_qdot_pipeline_pass_accounting():
    """Fast-tier non-regression gate: the fused+packed train step is ONE
    forward pallas_call + ONE backward-pair pallas_call per quantized layer
    (BENCH_kernels.json mirrors this; the CI fast tier runs this test)."""
    x, w = _rand(32, 128, 16, 19)
    fwd = count_pallas_calls(lambda x, w: qdot(x, w, _cfg()), x, w)
    assert fwd == 1  # FWD GEMM, quantization fused in
    assert _train_passes(_cfg(), x, w) <= 2  # FWD + backward pair — no more
    # one fewer pass per layer than the PR-1 fused pipeline (FWD+BWD+GRAD)...
    assert _train_passes(_cfg(pack=False), x, w) <= 3
    # ...and half the unfused oracle, which pays 3 for the forward alone
    unfused = count_pallas_calls(
        lambda x, w: qdot(x, w, _cfg(fused=False)), x, w)
    assert unfused == 3
    assert _train_passes(_cfg(fused=False), x, w) == 6


def test_qdot_packed_residual_bytes_drop_4x():
    # the acceptance measurement: activation-residual bytes per dense layer
    # drop >= 3.5x (exactly 4x: int8 codes vs f32 carriers), measured on the
    # residual pytree the custom_vjp actually saves
    t, k, n = 48, 256, 24
    x, w = _rand(t, k, n, 37)

    def res_bytes(cfg):
        _, res = _qdot2d_fwd(x, w, _encode_seed(0), cfg)
        return sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(res))

    packed = res_bytes(_cfg())
    carrier = res_bytes(_cfg(pack=False))
    assert packed == t * k + k * n  # int8: 1 byte per residual element
    assert carrier == 4 * (t * k + k * n)
    assert carrier >= 3.5 * packed
    # and the packed residuals decode to exactly the f32-carrier residuals
    (_, res_p), (_, res_c) = (_qdot2d_fwd(x, w, _encode_seed(0), _cfg()),
                              _qdot2d_fwd(x, w, _encode_seed(0), _cfg(pack=False)))
    for qt, arr in zip(res_p, res_c):
        assert isinstance(qt, QTensor)
        np.testing.assert_array_equal(np.asarray(qt.unpack()), np.asarray(arr))


def test_qdot_fused_equals_unfused_reference_bitexact():
    x, w = _rand(48, 256, 24, 23)
    y_f = qdot(x, w, _cfg())
    y_u = qdot(x, w, _cfg(fused=False))
    np.testing.assert_array_equal(np.asarray(y_f), np.asarray(y_u))

    def loss(cfg):
        return lambda x, w: jnp.sum(jnp.sin(qdot(x, w, cfg)))

    # packed QTensor residuals + one-pass backward vs f32 carriers + three
    # separate passes: forward AND both gradients bit-identical
    g_f = jax.grad(loss(_cfg()), argnums=(0, 1))(x, w)
    g_u = jax.grad(loss(_cfg(fused=False)), argnums=(0, 1))(x, w)
    for a, b in zip(g_f, g_u):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the f32-carrier fused path is the same function too
    g_c = jax.grad(loss(_cfg(pack=False)), argnums=(0, 1))(x, w)
    for a, b in zip(g_c, g_u):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_qdot_out_fmt_fused_equals_oracle():
    # consumer-format epilogue: fused == oracle (post-hoc quantize pass),
    # forward and both backward gradients (straight-through in both)
    x, w = _rand(40, 192, 24, 41)
    y_f = qdot(x, w, _cfg(out_fmt=FP8_152))
    y_u = qdot(x, w, _cfg(fused=False, out_fmt=FP8_152))
    np.testing.assert_array_equal(np.asarray(y_f), np.asarray(y_u))
    np.testing.assert_array_equal(
        np.asarray(y_f),
        np.asarray(quantize(qdot(x, w, _cfg()), FP8_152)))

    def loss(cfg):
        return lambda x, w: jnp.sum(jnp.sin(qdot(x, w, cfg)))

    g_f = jax.grad(loss(_cfg(out_fmt=FP8_152)), argnums=(0, 1))(x, w)
    g_u = jax.grad(loss(_cfg(fused=False, out_fmt=FP8_152)), argnums=(0, 1))(x, w)
    for a, b in zip(g_f, g_u):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_qdot_packed_output_for_the_wire():
    # serve-path carrier: qdot_packed emits int8 codes of out_fmt directly
    # from the GEMM epilogue — decoded, identical to qdot + quantize
    x, w = _rand(32, 128, 16, 43)
    qt = qdot_packed(x, w, _cfg(out_fmt=FP8_152))
    assert isinstance(qt, QTensor) and qt.payload.dtype == jnp.int8
    want = quantize(qdot(x, w, _cfg()), FP8_152)
    np.testing.assert_array_equal(np.asarray(qt.unpack()), np.asarray(want))
    # one pallas_call, no standalone output-quantization pass
    assert count_pallas_calls(
        lambda x, w: qdot_packed(x, w, _cfg(out_fmt=FP8_152)).payload, x, w) == 1


def test_qdot_wide_repr_fmt_keeps_f32_carriers():
    # (1,6,9) does not fit an int8 code: pack_residuals must quietly keep
    # the f32 carrier and stay bit-exact vs the oracle (lm_head case)
    x, w = _rand(16, 64, 8, 47)
    wide = FPFormat(e=6, m=9)
    y_f = qdot(x, w, _cfg(repr_fmt=wide))
    y_u = qdot(x, w, _cfg(repr_fmt=wide, fused=False))
    np.testing.assert_array_equal(np.asarray(y_f), np.asarray(y_u))
    g_f = jax.grad(lambda x, w: jnp.sum(qdot(x, w, _cfg(repr_fmt=wide))),
                   argnums=(0, 1))(x, w)
    g_u = jax.grad(lambda x, w: jnp.sum(qdot(x, w, _cfg(repr_fmt=wide, fused=False))),
                   argnums=(0, 1))(x, w)
    for a, b in zip(g_f, g_u):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_qdot_fused_no_repr_fmt_keeps_accumulation_semantics():
    # accumulation-only study: no input quantization, narrow carry only
    x, w = _rand(64, 256, 32, 29)
    cfg = QDotConfig(fwd=GEMMPrecision(m_acc=4, e_acc=6, chunk=64),
                     repr_fmt=None)
    y = qdot(x, w, cfg)
    want = qmatmul_pallas(x, w, e_acc=6, m_acc=4, block_k=64)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(want))
    # grads flow through the wide BWD/GRAD paths
    g = jax.grad(lambda x, w: jnp.sum(qdot(x, w, cfg)), argnums=(0, 1))(x, w)
    g_ref = jax.grad(lambda x, w: jnp.sum(x @ w), argnums=(0, 1))(x, w)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_qdot_batched_leading_dims_fused():
    rng = np.random.RandomState(31)
    x = jnp.asarray(rng.standard_normal((2, 3, 5, 64)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((64, 8)).astype(np.float32))
    y = qdot(x, w, _cfg())
    assert y.shape == (2, 3, 5, 8)
    x2 = x.reshape(-1, 64)
    want = qdot(x2, w, _cfg()).reshape(2, 3, 5, 8)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(want))
