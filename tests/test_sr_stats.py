"""Stochastic-rounding carries: determinism, cross-variant bit identity and
statistical unbiasedness (the tentpole's proof obligations).

The SR contract under test:

* ``rounding="rne"`` (the default) is bit-identical to the pre-SR kernels —
  the carry formula, the residual pytree and the masked-block predication
  are untouched when SR is off;
* a fixed ``sr_seed`` is deterministic: same seed -> same bits, across
  repeated calls, across block decompositions (the dither is a pure
  function of (seed, chunk-step, logical element), never of the tile
  schedule) and across the kernel variants (fused forward, backward pair,
  N-split backward pair, stats epilogue);
* the seeded dither is STATISTICALLY unbiased: the ensemble mean of SR
  runs over seeds converges to the ideal-f32 product of the quantized
  operands, within the computed confidence interval, where RNE at the
  same width carries a systematic swamping bias.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.bwd_pair import qmatmul_bwd_pair, qmatmul_bwd_pair_nsplit
from repro.kernels.common import N_STATS, carry_update, quantize_block
from repro.kernels.fused import qmatmul_fused
from repro.kernels.ops import QDotConfig, qdot, sr_role_seed
from repro.core.policy import GEMMPrecision
from repro.quant.formats import FP8_152
from repro.quant.qnum import quantize

ACC = (6, 5)  # narrow enough that carry rounding is visible everywhere

# pinned on PRs; the nightly sr-frontier CI job date-rotates this — every
# seed-agnostic contract below (determinism, decomposition invariance,
# cross-variant identity) must hold for ANY seed, so rotation is free fuzz
SR_SEED = int(os.environ.get("REPRO_SR_SEED", "7"))


def _operands(seed=0, t=96, k=160, n=80):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.standard_normal((t, k)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((k, n)).astype(np.float32))
    return x, w


# ------------------------------ RNE parity ---------------------------------


def test_rne_explicit_is_default_bitwise():
    x, w = _operands()
    base = qmatmul_fused(x, w, repr_fmt=FP8_152, e_acc=ACC[0], m_acc=ACC[1],
                         block_k=32)
    rne = qmatmul_fused(x, w, repr_fmt=FP8_152, e_acc=ACC[0], m_acc=ACC[1],
                        block_k=32, rounding="rne", sr_seed=123)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(rne))


def test_rne_carry_update_is_plain_quantize():
    # the RNE carry is the pre-SR formula: quantize_block(prev + partial)
    rng = np.random.RandomState(3)
    prev = jnp.asarray(rng.standard_normal((8, 16)).astype(np.float32))
    part = jnp.asarray(rng.standard_normal((8, 16)).astype(np.float32))
    got = carry_update(prev, part, e_acc=ACC[0], m_acc=ACC[1],
                       rounding="rne", seed_ref=None, step=0,
                       row0=0, col0=0, n_cols=16)
    want = quantize_block(prev + part, ACC[0], ACC[1])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_qdot_rne_default_parity_with_grads():
    x, w = _operands(1, 64, 128, 48)
    prec = GEMMPrecision(m_acc=ACC[1], e_acc=ACC[0], chunk=32)
    base = QDotConfig(fwd=prec, bwd=prec, grad=prec, repr_fmt=FP8_152)
    expl = QDotConfig(fwd=prec, bwd=prec, grad=prec, repr_fmt=FP8_152,
                      rounding="rne", sr_seed=99)

    def loss(cfg):
        def f(xx, ww):
            return jnp.sum(qdot(xx, ww, cfg) ** 2)
        y = qdot(x, w, cfg)
        gx, gw = jax.grad(f, argnums=(0, 1))(x, w)
        return y, gx, gw

    for a, b in zip(loss(base), loss(expl)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_invalid_rounding_rejected():
    x, w = _operands()
    with pytest.raises(ValueError):
        qmatmul_fused(x, w, e_acc=6, m_acc=5, rounding="nearest")


# -------------------------- seeded determinism -----------------------------


def test_sr_deterministic_and_seed_sensitive():
    x, w = _operands()
    kw = dict(repr_fmt=FP8_152, e_acc=ACC[0], m_acc=ACC[1], block_k=32,
              rounding="sr")
    y1 = qmatmul_fused(x, w, sr_seed=SR_SEED, **kw)
    y2 = qmatmul_fused(x, w, sr_seed=SR_SEED, **kw)
    y3 = qmatmul_fused(x, w, sr_seed=SR_SEED + 1, **kw)
    rne = qmatmul_fused(x, w, repr_fmt=FP8_152, e_acc=ACC[0], m_acc=ACC[1],
                        block_k=32)
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
    assert not np.array_equal(np.asarray(y1), np.asarray(y3))
    assert not np.array_equal(np.asarray(y1), np.asarray(rne))


def test_sr_invariant_to_block_decomposition():
    # the dither keys on logical coordinates, not the tile schedule
    x, w = _operands()
    kw = dict(repr_fmt=FP8_152, e_acc=ACC[0], m_acc=ACC[1], block_k=32,
              rounding="sr", sr_seed=SR_SEED)
    a = qmatmul_fused(x, w, block_m=32, block_n=32, **kw)
    b = qmatmul_fused(x, w, block_m=64, block_n=64, **kw)
    c = qmatmul_fused(x, w, block_m=128, block_n=128, **kw)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


def test_qdot_sr_matches_direct_fused_call():
    # qdot's per-role seed derivation is the documented public contract
    x, w = _operands(2, 64, 128, 48)
    prec = GEMMPrecision(m_acc=ACC[1], e_acc=ACC[0], chunk=32)
    cfg = QDotConfig(fwd=prec, repr_fmt=FP8_152, rounding="sr",
                     sr_seed=SR_SEED)
    y = qdot(x, w, cfg)
    direct = qmatmul_fused(x, w, repr_fmt=FP8_152, e_acc=ACC[0],
                           m_acc=ACC[1], block_k=32, rounding="sr",
                           sr_seed=sr_role_seed(SR_SEED, "fwd"))
    np.testing.assert_array_equal(np.asarray(y), np.asarray(direct))


def test_qdot_sr_requires_fused():
    x, w = _operands(2, 32, 64, 32)
    prec = GEMMPrecision(m_acc=ACC[1], e_acc=ACC[0], chunk=32)
    cfg = QDotConfig(fwd=prec, repr_fmt=FP8_152, rounding="sr", fused=False)
    with pytest.raises(ValueError):
        qdot(x, w, cfg)


def test_qdot_traced_seed_no_retrace():
    # per-step seeds ride through jit as a traced operand: ONE compile
    x, w = _operands(2, 32, 64, 32)
    prec = GEMMPrecision(m_acc=ACC[1], e_acc=ACC[0], chunk=32)
    cfg = QDotConfig(fwd=prec, repr_fmt=FP8_152, rounding="sr")

    @jax.jit
    def step(seed):
        return qdot(x, w, cfg, sr_seed=seed)

    a = step(jnp.uint32(5))
    b = step(jnp.uint32(5))
    c = step(jnp.uint32(6))
    assert step._cache_size() == 1
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(a), np.asarray(c))


# ------------------------ cross-variant bit identity -----------------------


def test_sr_backward_pair_matches_fused_gemms():
    # one seed, three kernels: the pair kernel's dx/dw carries draw the
    # SAME dither the standalone fused GEMMs draw at those coordinates
    x, w = _operands()
    rng = np.random.RandomState(9)
    g = jnp.asarray(rng.standard_normal((x.shape[0], w.shape[1]))
                    .astype(np.float32))
    xq, wq = quantize(x, FP8_152), quantize(w, FP8_152)
    sb, sg = SR_SEED + 101, SR_SEED + 202
    dx_p, dw_p = qmatmul_bwd_pair(
        g, xq, wq, repr_fmt=FP8_152, bwd_acc=ACC, grad_acc=ACC,
        block_t=32, block_k=32, block_n=32, packed=False,
        rounding="sr", sr_seed_bwd=sb, sr_seed_grad=sg)
    gq = quantize(g, FP8_152)
    dx_f = qmatmul_fused(gq, wq.T, e_acc=ACC[0], m_acc=ACC[1], block_k=32,
                         quantize_a=False, quantize_b=False,
                         rounding="sr", sr_seed=sb)
    dw_f = qmatmul_fused(xq.T, gq, e_acc=ACC[0], m_acc=ACC[1], block_k=32,
                         quantize_a=False, quantize_b=False,
                         rounding="sr", sr_seed=sg)
    np.testing.assert_array_equal(np.asarray(dx_p), np.asarray(dx_f))
    np.testing.assert_array_equal(np.asarray(dw_p), np.asarray(dw_f))


def test_sr_nsplit_matches_pair():
    x, w = _operands()
    rng = np.random.RandomState(10)
    g = jnp.asarray(rng.standard_normal((x.shape[0], w.shape[1]))
                    .astype(np.float32))
    xq, wq = quantize(x, FP8_152), quantize(w, FP8_152)
    kw = dict(repr_fmt=FP8_152, bwd_acc=ACC, grad_acc=ACC, block_t=32,
              block_k=32, block_n=32, packed=False, rounding="sr",
              sr_seed_bwd=SR_SEED + 101, sr_seed_grad=SR_SEED + 202)
    dx_p, dw_p = qmatmul_bwd_pair(g, xq, wq, **kw)
    dx_n, dw_n = qmatmul_bwd_pair_nsplit(g, xq, wq, n_split=2, **kw)
    np.testing.assert_array_equal(np.asarray(dx_p), np.asarray(dx_n))
    np.testing.assert_array_equal(np.asarray(dw_p), np.asarray(dw_n))


def test_sr_stats_epilogue_neutral():
    # telemetry on/off must not perturb the SR output either, and the raw
    # stats vector carries the two appended error moments
    x, w = _operands()
    kw = dict(repr_fmt=FP8_152, e_acc=ACC[0], m_acc=ACC[1], block_k=32,
              rounding="sr", sr_seed=SR_SEED)
    plain = qmatmul_fused(x, w, **kw)
    with_stats, raw = qmatmul_fused(x, w, collect_stats=True, **kw)
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(with_stats))
    assert raw.shape == (N_STATS,)


# -------------------- Monte-Carlo unbiasedness (satellite) -----------------


@pytest.mark.slow
def test_sr_ensemble_mean_unbiased_vs_f32_oracle():
    """E_seed[SR GEMM] -> f32 oracle of the QUANTIZED operands, within the
    computed CI; RNE at the same narrow width carries a systematic bias the
    SR ensemble mean does not."""
    rng = np.random.RandomState(1)
    M, K, N = 8, 2048, 8
    x = jnp.asarray(rng.standard_normal((M, K)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((K, N)).astype(np.float32))
    xq, wq = quantize(x, FP8_152), quantize(w, FP8_152)
    oracle = np.asarray(xq @ wq)  # ideal f32 product of what the kernel sees

    S = 48
    kw = dict(repr_fmt=FP8_152, e_acc=6, m_acc=4, block_k=64, rounding="sr")
    ys = np.stack([np.asarray(qmatmul_fused(x, w, sr_seed=s, **kw))
                   for s in range(S)])
    mean = ys.mean(0)
    stderr = ys.std(0, ddof=1) / np.sqrt(S)
    z = np.abs(mean - oracle) / np.maximum(stderr, 1e-12)
    # 64 cells, 48 seeds: an unbiased estimator keeps every |z| modest
    # (observed max ~3.6); a deterministic bias of RNE's size would give
    # |z| ~ bias/stderr ~ 30
    assert z.max() < 6.0, f"max |z| = {z.max():.2f}"
    assert z.mean() < 1.5, f"mean |z| = {z.mean():.2f}"

    rne = np.asarray(qmatmul_fused(x, w, repr_fmt=FP8_152, e_acc=6, m_acc=4,
                                   block_k=64))
    assert np.abs(mean - oracle).mean() < 0.5 * np.abs(rne - oracle).mean()
