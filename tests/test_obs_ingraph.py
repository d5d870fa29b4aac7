"""In-graph numerics telemetry (repro.obs.ingraph).

The two hard correctness constraints pinned here:

* tagging a model's QuantPlan (``stats_tag``) changes NOTHING about the
  numerics — one train step of the tagged qwen2 smoke model is bitwise
  identical (every state leaf + the loss) to the untagged step, because
  stats ride out of the *backward rule* (the pair kernel's
  ``collect_stats`` epilogue for BWD/GRAD, a residual replay for FWD)
  and the forward path is untouched;
* the collected windows are REAL controller food: driving the PR-3
  closed loop from a jitted ``jax.grad`` — true cotangents, no synthetic
  probe — restores a deliberately under-provisioned GRAD accumulator to
  within 1 bit of the closed-form bound within 3 cadence ticks.

Plus the plumbing: collector merge semantics, probe-contract geometry
(fwd n=K, bwd n=N, grad n=T), ``EnsembleStats.to_raw`` round-trip, drop
semantics outside ``collecting()``, and the ``stats_axis`` psum path on a
one-device mesh.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.policy import AccumulationPolicy, GEMMPrecision
from repro.core.precision import min_m_acc
from repro.kernels.ops import QDotConfig, qdot
from repro.obs.ingraph import (
    InGraphCollector,
    collecting,
    tag_quant_plan,
)
from repro.quant.formats import FP8_152
from repro.telemetry.controller import ControllerConfig, PrecisionController
from repro.telemetry.stats import EnsembleStats

CHUNK = 64


def _prec(m_acc, chunk=CHUNK):
    return GEMMPrecision(m_acc=m_acc, e_acc=6, chunk=chunk)


def _rand(m, k, n, seed):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.standard_normal((m, k)).astype(np.float32)),
            jnp.asarray(rng.standard_normal((k, n)).astype(np.float32)))


def _grad_fn(cfg):
    def loss(x, w):
        return 0.5 * jnp.sum(qdot(x, w, cfg) ** 2)

    return jax.jit(jax.grad(loss, argnums=(0, 1)))


def _collect_grad(cfg, x, w):
    f = _grad_fn(cfg)
    col = InGraphCollector()
    with collecting(col):
        out = f(x, w)
        jax.block_until_ready(out)
        jax.effects_barrier()
    return out, col


# --------------------------------------------------------------------------
# geometry + emission semantics on a single tagged qdot
# --------------------------------------------------------------------------


def test_tagged_qdot_emits_probe_contract_geometry():
    T, K, N = 40, 128, 24
    x, w = _rand(T, K, N, 0)
    cfg = QDotConfig(fwd=_prec(6), bwd=_prec(5), grad=_prec(8),
                     repr_fmt=FP8_152, stats_tag="layer0")
    _, col = _collect_grad(cfg, x, w)
    probes = col.probes()
    assert set(probes) == {("layer0", "fwd"), ("layer0", "bwd"),
                           ("layer0", "grad")}
    # same geometry contract as the eager probe path (probe_gemm)
    assert probes[("layer0", "fwd")].n == K
    assert probes[("layer0", "bwd")].n == N
    assert probes[("layer0", "grad")].n == T
    assert probes[("layer0", "grad")].m_acc == 8
    for p in probes.values():
        assert p.n1 == CHUNK
        assert float(p.stats.count) > 0
        # rounding noise can push the quantized variance a hair past the
        # ideal ensemble's, so vrr can exceed 1.0 slightly
        assert 0.0 < float(p.stats.measured_vrr) <= 1.01


def test_tagged_dx_dw_bitwise_match_untagged():
    x, w = _rand(48, 256, 32, 1)
    base = QDotConfig(fwd=_prec(6), bwd=_prec(5), grad=_prec(7),
                      repr_fmt=FP8_152)
    from dataclasses import replace

    (dx0, dw0) = _grad_fn(base)(x, w)
    (dx1, dw1), col = _collect_grad(replace(base, stats_tag="t"), x, w)
    np.testing.assert_array_equal(np.asarray(dx0), np.asarray(dx1))
    np.testing.assert_array_equal(np.asarray(dw0), np.asarray(dw1))
    assert len(col) == 3


def test_emissions_drop_outside_collecting_and_when_untagged():
    x, w = _rand(16, 64, 8, 2)
    tagged = QDotConfig(fwd=_prec(6), repr_fmt=FP8_152, stats_tag="t")
    _grad_fn(tagged)(x, w)
    jax.effects_barrier()  # tagged but no active collector: dropped, no error

    untagged = QDotConfig(fwd=_prec(6), repr_fmt=FP8_152)
    _, col = _collect_grad(untagged, x, w)
    assert len(col) == 0


def test_collector_sum_merges_repeated_emissions():
    x, w = _rand(32, 128, 16, 3)
    cfg = QDotConfig(fwd=_prec(6), repr_fmt=FP8_152, stats_tag="shared")
    f = _grad_fn(cfg)
    col = InGraphCollector()
    with collecting(col):
        for _ in range(3):
            jax.block_until_ready(f(x, w))
        jax.effects_barrier()
    cell = col._cells[("shared", "fwd")]
    assert cell["emissions"] == 3
    # 3 identical windows sum-merge to 3x the count, same mean/vrr
    _, one = _collect_grad(cfg, x, w)
    p3 = col.probes()[("shared", "fwd")]
    p1 = one.probes()[("shared", "fwd")]
    assert float(p3.stats.count) == 3 * float(p1.stats.count)
    np.testing.assert_allclose(float(p3.stats.measured_vrr),
                               float(p1.stats.measured_vrr), rtol=1e-5)


def test_to_raw_round_trips_ensemble_stats():
    x, w = _rand(64, 256, 24, 4)
    from repro.telemetry.stats import gemm_stats

    _, st = gemm_stats(x, w, precision=_prec(6), repr_fmt=FP8_152)
    rt = EnsembleStats.from_raw(np.asarray(st.to_raw(), np.float64))
    assert float(rt.count) == float(st.count)
    for attr in ("mean_q", "mean_i", "max_abs", "swamped", "adds"):
        np.testing.assert_allclose(float(getattr(rt, attr)),
                                   float(getattr(st, attr)), rtol=1e-5,
                                   atol=1e-7)
    np.testing.assert_allclose(float(rt.var_q), float(st.var_q), rtol=1e-4)
    np.testing.assert_allclose(float(rt.measured_vrr),
                               float(st.measured_vrr), rtol=1e-4)


def test_stats_axis_psums_and_masks_to_shard_zero():
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1,), ("x",))
    x, w = _rand(64, 128, 16, 5)
    plain = QDotConfig(fwd=_prec(6), repr_fmt=FP8_152, stats_tag="t")
    from dataclasses import replace

    meshed = replace(plain, stats_axis="x")

    def gfn(x, w):
        return jax.grad(lambda a, b: jnp.sum(qdot(a, b, meshed)))(x, w)

    f = jax.jit(shard_map(gfn, mesh=mesh, in_specs=(P(), P()),
                          out_specs=P(), check_vma=False))
    col = InGraphCollector()
    with collecting(col):
        jax.block_until_ready(f(x, w))
        jax.effects_barrier()
    # one shard: psum is the identity, the mask keeps exactly one emission
    assert len(col) == 1
    cell = col._cells[("t", "fwd")]
    assert cell["emissions"] == 1
    _, ref = _collect_grad(plain, x, w)
    np.testing.assert_allclose(cell["row"],
                               ref._cells[("t", "fwd")]["row"], rtol=1e-5)


# --------------------------------------------------------------------------
# the model-level pin: tagged train step is bit-identical + fully covered
# --------------------------------------------------------------------------


def test_tagged_train_step_bit_identical_and_covers_plan():
    from repro.configs import get_smoke_config
    from repro.core.policy import plan_for_model
    from repro.data.pipeline import DataConfig, SyntheticLM, with_extras
    from repro.models.api import get_model
    from repro.models.layers import Dist
    from repro.telemetry.controller import PLAN_FIELDS, ROLES
    from repro.train.loop import TrainConfig, init_train_state, make_train_step

    policy = AccumulationPolicy(mode="perturbed", perturbation=-2, chunk=64)
    cfg = plan_for_model(get_smoke_config("qwen2-1.5b"), seq_len=16,
                         global_batch=2, policy=policy)
    model = get_model(cfg)
    tc = TrainConfig()
    state = init_train_state(model, jax.random.PRNGKey(0), tc)
    batch = with_extras(next(SyntheticLM(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=16, global_batch=2))), cfg)

    s0, m0 = jax.jit(make_train_step(model, tc, Dist()))(state, batch)

    tagged = get_model(tag_quant_plan(cfg))
    fn = jax.jit(make_train_step(tagged, tc, Dist()))
    col = InGraphCollector()
    with collecting(col):
        s1, m1 = fn(state, batch)
        jax.block_until_ready((s1, m1))
        jax.effects_barrier()

    # bit parity: every state leaf and the loss
    assert float(m0["loss"]) == float(m1["loss"])
    flat0 = jax.tree.leaves(s0)
    flat1 = jax.tree.leaves(s1)
    assert len(flat0) == len(flat1)
    for a, b in zip(flat0, flat1):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # full coverage: every quantized plan field x every planned role
    probes = col.probes()
    expected = set()
    for name in PLAN_FIELDS:
        qcfg = getattr(cfg.quant, name, None)
        if qcfg is None or qcfg.is_exact:
            continue
        for role in ROLES:
            if getattr(qcfg, role, None) is not None:
                expected.add((name, role))
    assert set(probes) == expected and len(expected) >= 15
    for (name, role), p in probes.items():
        assert float(p.stats.count) > 0, (name, role)


# --------------------------------------------------------------------------
# the closed-loop gate on TRUE gradients
# --------------------------------------------------------------------------


def test_controller_converges_from_true_ingraph_gradients(tmp_path):
    """The acceptance gate: a GRAD accumulator provisioned 2 bits under
    the closed-form bound, measured ONLY from io_callback'd windows of a
    jitted ``jax.grad`` (cotangent = the true upstream gradient), is
    restored to within 1 bit of the bound in <= 3 cadence ticks."""
    T, K, N = 16384, 32, 16  # GRAD accumulates over T: n2 = T/CHUNK = 256
    x = jax.random.normal(jax.random.PRNGKey(0), (T, K), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (K, N), jnp.float32)
    # a linear readout: the cotangent reaching the tagged qdot is exactly
    # dL/dy = c — a TRUE autodiff gradient, while keeping dw = x.T @ c on
    # zero-mean independent products, the regime the closed-form bound
    # prices.  (A quadratic loss correlates the cotangent with x; the
    # resulting non-zero-mean accumulation swamps HARDER than the bound —
    # which the loop handles, but then m_pred is not the fixed point this
    # gate pins.)
    c = jax.random.normal(jax.random.PRNGKey(2), (T, N), jnp.float32)
    m_pred = min_m_acc(T, 5, chunked=True, chunk=CHUNK)
    log = str(tmp_path / "ingraph.jsonl")
    ctl = PrecisionController(
        AccumulationPolicy(mode="predicted", chunk=CHUNK),
        ControllerConfig(cadence=1, hysteresis=1), log_path=log)

    m = m_pred - 2
    history = []
    for step in range(1, 4):  # the gate: converged within 3 ticks
        cfg = QDotConfig(fwd=_prec(12), bwd=_prec(12), grad=_prec(m),
                         repr_fmt=FP8_152, stats_tag="layer")
        f = jax.jit(jax.grad(lambda a, b: jnp.sum(qdot(a, b, cfg) * c),
                             argnums=(0, 1)))
        col = InGraphCollector()
        with collecting(col):
            jax.block_until_ready(f(x, w))
            jax.effects_barrier()
        events = ctl.observe(step, col.probes())
        ev = next(e for e in events
                  if e["gemm"] == "layer" and e["role"] == "grad")
        history.append((step, ev["event"], ev["m_acc"]))
        m = ev["m_acc"]
        if ev["event"] == "ok":
            break
    assert history[0][1] == "bump", (
        f"tick 1 did not detect the under-provisioned width: {history}")
    assert history[-1][1] == "ok", (
        f"did not converge within 3 true-gradient ticks: {history}")
    assert abs(m - m_pred) <= 1, f"ended at {m}, bound {m_pred}: {history}"
