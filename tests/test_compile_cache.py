"""Compile-count regression tests: ONE trace per (bucket, kernel).

The retrace tax this subsystem closes: every new slab geometry used to
cost a fresh trace+compile (``q_offset``/``kv_offset``/``t0`` were
jit-static and the history operand grew with every slab), so a novel
prompt shape paid O(prompt/chunk) compiles before its first token.  The
bucketed paged-prefill kernel takes its geometry as scalar-prefetch
operands against a padded page row, so one compiled kernel serves every
slab of every prompt in a bucket.  Pinned here at three levels:

* kernel — 20+ randomized (t0, q_len) slab geometries, aligned and
  ragged, through ``flash_prefill_paged`` cost exactly ONE trace and
  each matches the one-shot walk of its jnp oracle
  (``flash_prefill_paged_reference``) bit-for-bit;
* engine — a warmed ``ServeEngine`` serves randomized traffic including
  ragged tails and post-preemption restores with ZERO steady-state
  compiles (the serve bench gates the same number in CI);
* planner — knee certification is memoized per (bucket geometry, width):
  the evaluation count is O(#buckets) and does not grow with traffic.

(The legacy-shim parity test that lived here retired with the PR-6 shims
at version 0.2; tests/test_shims.py pins that they stay gone.)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.kernels.attention import (
    counting_traces,
    flash_prefill_paged,
    flash_prefill_paged_reference,
)
from repro.models.api import get_model
from repro.serve import plan as P
from repro.serve.scheduler import ServeEngine

ACC = (6, 7)


@pytest.fixture(scope="module")
def smoke_model():
    cfg = get_smoke_config("qwen2-1.5b")
    model = get_model(cfg)
    return model, model.init_params(jax.random.PRNGKey(0))


# --------------------------------------------------------------------------
# kernel level: one compiled signature serves every slab geometry
# --------------------------------------------------------------------------


def test_one_trace_serves_all_slab_geometries():
    """20+ randomized (t0, q_len) geometries — page-aligned offsets,
    ragged tails, single-row slabs — through ONE (bucket-width, slab-width)
    signature: exactly one trace, every output bit-equal to the oracle's
    one-shot walk over the same prefix."""
    chunk, W, T = 4, 6, 8          # page size, bucket page width, slab width
    h, kv, dh = 4, 2, 8
    max_ctx = W * chunk
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.standard_normal((max_ctx, h, dh)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((max_ctx, kv, dh)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((max_ctx, kv, dh)).astype(np.float32))
    kp = jnp.reshape(k, (W, chunk, kv, dh)).transpose(0, 2, 1, 3)
    vp = jnp.reshape(v, (W, chunk, kv, dh)).transpose(0, 2, 1, 3)
    se = jnp.zeros((W,), jnp.int32)
    row = jnp.arange(W, dtype=jnp.int32)

    geoms = [(0, 8), (0, 5), (0, 4), (0, 1), (4, 8), (4, 4), (4, 3),
             (4, 1), (8, 8), (8, 4), (8, 2), (8, 1), (12, 8), (12, 6),
             (12, 1), (16, 8), (16, 7), (16, 4), (16, 2), (20, 4),
             (20, 3), (20, 1)]
    assert len(geoms) >= 20
    # scoped trace counting (no global reset): composes under any ordering
    with counting_traces() as counts:
        for t0, q_len in geoms:
            q_len = min(q_len, max_ctx - t0)
            kv_len = t0 + q_len
            qs = jnp.zeros((T, h, dh), jnp.float32).at[:q_len].set(q[t0:kv_len])
            out = flash_prefill_paged(
                qs, kp, vp, se, se, row, jnp.int32(t0), jnp.int32(q_len),
                jnp.int32(kv_len), kv_fmt=None, acc=ACC, block_q=T)
            one = flash_prefill_paged_reference(
                q[:kv_len], kp, vp, se, se, row, 0, kv_len, kv_len, acc=ACC)
            np.testing.assert_array_equal(np.asarray(out[:q_len]),
                                          np.asarray(one[t0:]))
            assert np.all(np.asarray(out[q_len:]) == 0.0), (t0, q_len)
    assert counts.get("flash_prefill_paged") == 1, counts


# --------------------------------------------------------------------------
# engine level: warmed cache, zero steady-state compiles
# --------------------------------------------------------------------------


def test_warmed_engine_zero_steady_state_compiles(smoke_model):
    """A warm-started engine serves 20+ randomized prompt/slab geometries
    (ragged tails, a forced mid-stream preemption + restore) without a
    single new trace: compile count frozen, every dispatch a hit, and the
    paged-prefill kernel's trace counter untouched."""
    model, params = smoke_model
    eng = ServeEngine(model, params, n_pages=10, page_size=4, max_batch=3,
                      prefill_chunk_tokens=4, warm_start=True)
    base = eng.compile_stats()
    assert base is not None and base["compiles"] > 0
    rng = np.random.RandomState(1)

    def burst(n_req):
        for _ in range(n_req):
            n = int(rng.randint(4, 21))          # ragged page tails included
            g = int(rng.randint(1, 5))
            eng.submit(list(rng.randint(1, model.cfg.vocab_size, n)), g)

    # scoped deltas instead of global resets: steady-state traffic must
    # add zero traces and zero compiles no matter what ran before
    with counting_traces() as traces, \
            eng.executor.compile_stats_scope() as delta:
        burst(4)
        for _ in range(4):
            eng.step()
        victim = max(eng.active) if eng.active else None
        if victim is not None:
            eng.preempt(victim)                  # post-preemption restore path
        eng.run()
        burst(4)
        eng.run()
    assert eng.prefill_slabs >= 20, "not enough slab geometries exercised"
    assert eng.restores >= 1, "the forced preemption was not restored"
    assert delta["compiles"] == 0, delta
    assert delta["misses"] == 0, delta
    assert delta["hits"] > 0, delta
    assert traces.get("flash_prefill_paged", 0) == 0, \
        "steady-state traffic re-traced the paged prefill kernel"


# --------------------------------------------------------------------------
# planner level: knee certification is O(#buckets), not O(traffic)
# --------------------------------------------------------------------------


def test_certification_memoized_per_bucket_geometry():
    P.reset_certification_stats()
    pl = P.plan_attention(4096, 16, prefill_chunk_tokens=64)
    ev0 = P.certification_stats()["evaluations"]
    assert ev0 > 0
    # one evaluation per candidate width per bucket, at most
    assert ev0 <= len(pl.buckets) * (23 - pl.m_p + 1)
    # identical re-plans (engine restarts, the bench's cold/warm pair) are
    # ALL memo hits
    for _ in range(5):
        P.plan_attention(4096, 16, prefill_chunk_tokens=64)
    s = P.certification_stats()
    assert s["evaluations"] == ev0 and s["hits"] > 0
    # the monitor's per-tick query costs one evaluation, ever
    before = P.certification_stats()["evaluations"]
    for _ in range(100):
        P.certified_log_v(7, 5, 16, 4096, 0)
    assert P.certification_stats()["evaluations"] <= before + 1


def test_certification_count_constant_over_fuzz_suite():
    """Regression for the O(#buckets) property over the scheduler fuzz
    suite: replaying the pinned bursty traces five times evaluates the
    knee test exactly as often as the FIRST replay did — traffic volume
    never re-certifies a bucket."""
    from repro.serve.sim import (
        BURSTY_POOL,
        BURSTY_TRACE,
        SimExecutor,
        poisson_burst_trace,
        replay_trace,
    )

    P.reset_certification_stats()

    def run(seed):
        ex = SimExecutor(n_pages=BURSTY_POOL["n_pages"],
                         page_size=BURSTY_POOL["page_size"], vocab_size=50)
        eng = ServeEngine(None, None, executor=ex, **BURSTY_POOL,
                          prefill_chunk_tokens=BURSTY_POOL["page_size"])
        replay_trace(eng, poisson_burst_trace(seed, **BURSTY_TRACE))

    run(11)
    ev_first = P.certification_stats()["evaluations"]
    for seed in (12, 13, 14, 15):
        run(seed)
    assert P.certification_stats()["evaluations"] == ev_first, \
        "knee certifications grew with traffic — memoization broke"


# The PR-6 legacy-shim parity test that lived here was retired with the
# shims themselves at version 0.2 (see tests/test_shims.py, which pins
# that the deprecated entry points stay gone).
