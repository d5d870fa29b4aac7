"""Swamping telemetry + closed-loop precision controller.

Covers the kernel<->telemetry contract (raw stats vector -> EnsembleStats),
the streaming reducers (Welford merge, mesh psum), the probe capture path,
the controller's hysteresis/bump/trim/pin semantics with its JSONL event
log, checkpoint round-trip of the realized schedule — and the fast-tier
smoke gate: on a deliberately under-provisioned synthetic layer the
controller must converge to within 1 bit of the closed-form bound.
"""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.policy import AccumulationPolicy, GEMMPrecision
from repro.core.precision import min_m_acc
from repro.core.vrr import CUTOFF_LOG_V
from repro.quant.formats import FP8_152
from repro.telemetry.controller import (
    ControllerConfig,
    GemmProbe,
    PrecisionController,
    apply_schedule,
)
from repro.telemetry.stats import EnsembleStats, bwd_pair_stats, gemm_stats

# the synthetic demo layer shared with benchmarks/telemetry_loop.py (same
# shapes + widths => shared jit cache within the test session)
N1, N2 = 64, 512
K_LEN = N1 * N2


def _rand(m, k, n, seed):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.standard_normal((m, k)).astype(np.float32)),
            jnp.asarray(rng.standard_normal((k, n)).astype(np.float32)))


def _prec(m_acc, chunk=64):
    return GEMMPrecision(m_acc=m_acc, e_acc=6, chunk=chunk)


# ------------------------- stats: kernel contract ---------------------------


def test_gemm_stats_moments_match_kernel_output():
    # the quantized-ensemble moments must be exactly the moments of the
    # emitted output (no out_fmt: the carry IS the output), and the counter
    # slots must cover exactly the valid region
    a, b = _rand(100, 300, 50, 0)
    y, st = gemm_stats(a, b, precision=_prec(6), repr_fmt=FP8_152)
    ynp = np.asarray(y, dtype=np.float64)
    assert float(st.count) == 100 * 50
    np.testing.assert_allclose(float(st.mean_q), ynp.mean(), rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(float(st.var_q), ynp.var(), rtol=1e-4,
                               atol=1e-6)
    # ideal ensemble: the f32 shadow accumulation of the same quantized
    # products — close to the wide-accumulation GEMM of the same operands
    from repro.kernels.fused import qmatmul_fused

    ideal = np.asarray(qmatmul_fused(a, b, repr_fmt=FP8_152), np.float64)
    np.testing.assert_allclose(float(st.var_i), ideal.var(), rtol=1e-3)
    assert float(st.adds) <= 100 * 50 * 5  # <= elements x chunks
    assert 0.0 <= float(st.swamp_rate) <= 1.0
    assert float(st.max_exponent) > 0.0


def test_collect_stats_off_is_bitexact_fused():
    from repro.kernels.fused import qmatmul_fused

    a, b = _rand(130, 257, 61, 1)
    base = np.asarray(qmatmul_fused(a, b, repr_fmt=FP8_152, e_acc=6,
                                    m_acc=7, block_k=64))
    y, _ = gemm_stats(a, b, precision=_prec(7), repr_fmt=FP8_152)
    np.testing.assert_array_equal(np.asarray(y), base)


def test_collect_stats_off_is_bitexact_bwd_pair():
    from repro.kernels.bwd_pair import qmatmul_bwd_pair
    from repro.quant.qnum import quantize
    from repro.quant.qtensor import pack_block

    rng = np.random.RandomState(5)
    g = jnp.asarray(rng.standard_normal((64, 48)).astype(np.float32))
    xq = pack_block(quantize(jnp.asarray(
        rng.standard_normal((64, 80)).astype(np.float32)), FP8_152), 5, 2)
    wq = pack_block(quantize(jnp.asarray(
        rng.standard_normal((80, 48)).astype(np.float32)), FP8_152), 5, 2)
    dx0, dw0 = qmatmul_bwd_pair(g, xq, wq, repr_fmt=FP8_152, bwd_acc=(6, 5),
                                grad_acc=(6, 8), block_t=64, block_n=64)
    dx1, dw1, sb, sg = bwd_pair_stats(g, xq, wq, repr_fmt=FP8_152,
                                      bwd=_prec(5), grad=_prec(8, chunk=64))
    np.testing.assert_array_equal(np.asarray(dx1), np.asarray(dx0))
    np.testing.assert_array_equal(np.asarray(dw1), np.asarray(dw0))
    assert float(sb.count) == 64 * 80 and float(sg.count) == 80 * 48


def test_stats_rejects_residual_emission_combo():
    from repro.kernels.fused import qmatmul_fused

    a, b = _rand(8, 8, 8, 2)
    with pytest.raises(ValueError, match="probe-path"):
        qmatmul_fused(a, b, repr_fmt=FP8_152, return_quantized=True,
                      collect_stats=True)


# --------------------------- streaming reducers -----------------------------


def test_welford_merge_equals_pooled_ensemble():
    a1, b = _rand(48, 256, 24, 3)
    a2, _ = _rand(48, 256, 24, 4)
    p = _prec(6)
    _, s1 = gemm_stats(a1, b, precision=p, repr_fmt=FP8_152)
    _, s2 = gemm_stats(a2, b, precision=p, repr_fmt=FP8_152)
    _, s12 = gemm_stats(jnp.concatenate([a1, a2]), b, precision=p,
                        repr_fmt=FP8_152)
    m = s1.merge(s2)
    assert float(m.count) == float(s12.count)
    np.testing.assert_allclose(float(m.mean_q), float(s12.mean_q),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(float(m.var_q), float(s12.var_q), rtol=1e-3)
    np.testing.assert_allclose(float(m.var_i), float(s12.var_i), rtol=1e-3)
    assert float(m.max_abs) == max(float(s1.max_abs), float(s2.max_abs))
    assert float(m.swamped) == float(s1.swamped) + float(s2.swamped)
    # merge is associative-ish with zero()
    z = EnsembleStats.zero().merge(s1)
    np.testing.assert_allclose(float(z.var_q), float(s1.var_q), rtol=1e-5)


def test_psum_matches_merge_across_shards():
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_mesh

    if len(jax.devices()) < 1:
        pytest.skip("no devices")
    mesh = make_mesh((1,), ("x",))
    a, b = _rand(32, 256, 16, 6)
    _, s = gemm_stats(a, b, precision=_prec(6), repr_fmt=FP8_152)

    def f(st):
        return st.psum("x")

    out = shard_map(f, mesh=mesh, in_specs=(P(),), out_specs=P(),
                    check_vma=False)(s)
    # single shard: psum must be the identity on the ensemble
    np.testing.assert_allclose(float(out.var_q), float(s.var_q), rtol=1e-5)
    assert float(out.count) == float(s.count)


# ------------------------------ probe capture -------------------------------


def test_probe_gemm_covers_all_roles():
    from repro.kernels.ops import QDotConfig
    from repro.telemetry.probe import probe_gemm

    x, w = _rand(40, 128, 24, 7)
    qcfg = QDotConfig(fwd=_prec(6), bwd=_prec(5), grad=_prec(8),
                      repr_fmt=FP8_152)
    out = probe_gemm(x, w, qcfg, key=jax.random.PRNGKey(0))
    assert set(out) == {"fwd", "bwd", "grad"}
    assert out["fwd"].n == 128 and out["bwd"].n == 24 and out["grad"].n == 40
    assert out["grad"].m_acc == 8
    for p in out.values():
        assert float(p.stats.count) > 0


def test_capture_records_only_eager_calls():
    from repro.kernels.ops import QDotConfig, qdot
    from repro.telemetry import capture

    x, w = _rand(16, 64, 8, 8)
    cfg = QDotConfig(fwd=_prec(6), repr_fmt=FP8_152)
    with capture.capture_gemms() as buf:
        qdot(x, w, cfg)                       # eager: recorded
        jax.jit(lambda a, b: qdot(a, b, cfg))(x, w)  # traced: not recorded
    assert len(buf) == 1
    assert buf[0]["x"].shape == (16, 64)
    assert not capture.active()


# ------------------------------- controller ---------------------------------


def _probe_for(m_acc, x, w):
    _, st = gemm_stats(x, w, precision=_prec(m_acc), repr_fmt=FP8_152)
    return GemmProbe(stats=st, n=K_LEN, n1=N1, m_acc=m_acc)


def test_controller_converges_on_underprovisioned_layer(tmp_path):
    """The CI smoke gate: start at solver bound - 2; the closed loop must
    restore m_acc to within 1 bit of the closed-form bound, logging JSONL."""
    m_pred = min_m_acc(K_LEN, 5, chunked=True, chunk=N1)
    x = jax.random.normal(jax.random.PRNGKey(0), (32, K_LEN), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (K_LEN, 32), jnp.float32)
    log = str(tmp_path / "telemetry.jsonl")
    ctl = PrecisionController(
        AccumulationPolicy(mode="predicted", chunk=N1),
        ControllerConfig(cadence=1, hysteresis=1), log_path=log)
    m = m_pred - 2
    for step in range(1, 9):
        ev = ctl.observe(step, {("layer", "grad"): _probe_for(m, x, w)})[0]
        m = ev["m_acc"]
        if ev["event"] == "ok":
            break
    assert abs(m - m_pred) <= 1, f"ended at {m}, bound {m_pred}"
    events = [json.loads(line) for line in open(log)]
    assert any(e["event"] == "bump" for e in events)
    # under-provisioning was detected on the FIRST cadence tick
    assert events[0]["step"] == 1 and events[0]["event"] == "bump"
    for key in ("gemm", "role", "event", "source", "m_acc", "m_pred",
                "measured_vrr", "predicted_vrr", "log_v", "log_v_pred",
                "cutoff", "swamp_rate", "max_exp", "n", "n1", "n2"):
        assert key in events[0], f"JSONL schema missing {key}"
    assert ctl.schedule()[("layer", "grad")] == m


def test_controller_hysteresis_and_trim_and_pin():
    over = EnsembleStats(
        count=jnp.float32(4096.0), mean_q=jnp.float32(0.0),
        m2_q=jnp.float32(4095.0), mean_i=jnp.float32(0.0),
        m2_i=jnp.float32(4096.0), max_abs=jnp.float32(64.0),
        swamped=jnp.float32(1.0), adds=jnp.float32(4096.0))
    policy = AccumulationPolicy(mode="predicted", chunk=64)
    ctl = PrecisionController(policy, ControllerConfig(hysteresis=2))
    m_pred = min_m_acc(K_LEN, 5, chunked=True, chunk=64)
    probe = GemmProbe(stats=over, n=K_LEN, n1=64, m_acc=m_pred + 3)
    # measured margin + above bound => trim, but only after 2 ticks
    e1 = ctl.observe(1, {("mlp_up", "grad"): probe})[0]
    assert e1["event"] == "ok"
    e2 = ctl.observe(2, {("mlp_up", "grad"): probe})[0]
    assert e2["event"] == "trim" and e2["m_acc"] == m_pred + 2
    # pinned gemms are never trimmed
    ctl2 = PrecisionController(policy, ControllerConfig(hysteresis=1))
    head = GemmProbe(stats=over, n=K_LEN, n1=64, m_acc=9)
    assert ctl2.observe(1, {("lm_head", "grad"): head})[0]["event"] == "ok"


def test_controller_meta_roundtrip_and_apply_schedule():
    from repro.configs import get_smoke_config

    policy = AccumulationPolicy(mode="predicted", chunk=64)
    ctl = PrecisionController(policy)
    ctl._schedule[("mlp_up", "grad")] = 11
    meta = ctl.to_meta()
    assert meta == {"mlp_up:grad": 11}
    ctl2 = PrecisionController(policy)
    ctl2.restore_meta(meta)
    assert ctl2.schedule() == {("mlp_up", "grad"): 11}

    cfg = apply_schedule(get_smoke_config("qwen2-1.5b"), policy,
                         {("mlp_up", "grad"): 11, ("lm_head", "fwd"): 99},
                         seq_len=32, global_batch=2)
    assert cfg.quant.mlp_up.grad.m_acc == 11
    assert cfg.quant.lm_head.fwd.m_acc == 23  # clamped to the f32 carrier
    # untouched roles keep the solver assignment
    base = apply_schedule(get_smoke_config("qwen2-1.5b"), policy, {},
                          seq_len=32, global_batch=2)
    assert cfg.quant.mlp_up.fwd == base.quant.mlp_up.fwd


def test_perturbed_policy_clamps_to_carrier():
    p = AccumulationPolicy(mode="perturbed", perturbation=40)
    sol = p.for_length(4096)
    assert sol.m_acc == 23
    # and the resulting kernel config is actually runnable
    a, b = _rand(16, 128, 8, 9)
    y, st = gemm_stats(a, b, precision=sol, repr_fmt=FP8_152)
    assert np.isfinite(np.asarray(y)).all()
    assert float(st.measured_vrr) == pytest.approx(1.0, abs=1e-3)
    down = AccumulationPolicy(mode="perturbed", perturbation=-40)
    assert down.for_length(4096).m_acc == 1


# --------------------------- telemetry train tick ---------------------------


def test_run_telemetry_tick_end_to_end(tmp_path):
    from repro.configs import get_smoke_config
    from repro.core.policy import plan_for_model
    from repro.models.api import get_model
    from repro.data.pipeline import DataConfig, SyntheticLM, with_extras
    from repro.train.loop import TrainConfig, init_train_state, run_telemetry_tick

    policy = AccumulationPolicy(mode="perturbed", perturbation=-2, chunk=64)
    cfg = plan_for_model(get_smoke_config("qwen2-1.5b"), seq_len=16,
                         global_batch=2, policy=policy)
    model = get_model(cfg)
    state = init_train_state(model, jax.random.PRNGKey(0), TrainConfig())
    batch = with_extras(next(SyntheticLM(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=16, global_batch=2))), cfg)
    ctl = PrecisionController(
        policy, ControllerConfig(cadence=1, hysteresis=1),
        log_path=str(tmp_path / "t.jsonl"))
    events, new_model = run_telemetry_tick(
        ctl, model, state, batch, step=1, key=jax.random.PRNGKey(1),
        seq_len=16, global_batch=2)
    # every plan field x role of the smoke model gets a verdict
    assert {(e["gemm"], e["role"]) for e in events} >= {
        ("attn_qkv", "fwd"), ("attn_qkv", "bwd"), ("attn_qkv", "grad"),
        ("mlp_up", "grad"), ("mlp_down", "bwd"), ("lm_head", "fwd")}
    if new_model is not None:  # any adjustment must re-plan coherently
        assert new_model.cfg.quant is not model.cfg.quant
