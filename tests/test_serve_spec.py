"""Speculative decoding on the REAL smoke models: numerics + engine.

The sim fuzz (``tests/test_serve_sim.py``) pins the scheduler-level
contract over 100+ interleavings; this file pins the model-level claims
that make it sound on real arenas:

* ``lm.paged_verify`` — logits AND post-append arena bitwise identical
  to ``k + 1`` sequential ``lm.paged_decode`` steps over the same pages;
* ``plan_verify`` — certifies every (bucket, k) of a healthy plan and
  refuses a doctored bucket (too-small ``max_ctx``, degraded ``e_acc``);
* rollback — ``truncate_pages`` after a speculative append leaves the
  arena bitwise identical to one that never appended;
* ``SpecDecodeEngine`` on the real smoke pair (qwen2-1.5b target,
  qwen2-0.5b draft) emits streams identical to a plain ``ServeEngine``
  — including the draft==target all-accept limit — and a warm-started
  spec engine serves steady-state traffic with ZERO new compiles (the
  ``serve-spec`` CI bench gates the same number).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import lm
from repro.models.api import get_model
from repro.quant.formats import FP8_152
from repro.serve import truncate_pages
from repro.serve.plan import plan_attention, plan_verify
from repro.serve.scheduler import ServeEngine
from repro.serve.spec import SpecDecodeEngine
from tests.conftest import layer_sliced_arena


@pytest.fixture(scope="module")
def smoke_pair():
    """(target model+params, draft model+params) — shared 256-token vocab."""
    tcfg = get_smoke_config("qwen2-1.5b")
    dcfg = get_smoke_config("qwen2-0.5b")
    assert tcfg.vocab_size == dcfg.vocab_size
    tm, dm = get_model(tcfg), get_model(dcfg)
    return (tm, tm.init_params(jax.random.PRNGKey(0)),
            dm, dm.init_params(jax.random.PRNGKey(7)))


# --------------------------------------------------------------------------
# kernel/model level: one verify pass == k+1 sequential decode steps
# --------------------------------------------------------------------------


def _prefilled_state(cfg, params, rng, rows, *, acc):
    """Prefill ``rows = [(pages, n_tokens)]`` into a fresh paged arena;
    returns (kv_state, per-row prompt token arrays)."""
    kv_state = lm.init_paged_state(cfg, n_pages=10, page_size=4)
    prompts = []
    for pages, n in rows:
        toks = jnp.asarray([rng.randint(0, cfg.vocab_size, n)], jnp.int32)
        pg_ids = jnp.asarray(pages, jnp.int32)
        _, kv_state = lm.paged_prefill(params, toks, kv_state, pg_ids,
                                       pg_ids, 0, n, cfg,
                                       kv_fmt=FP8_152, acc=acc)
        prompts.append(toks)
    return kv_state, prompts


def test_paged_verify_bitexact_vs_sequential_decode(smoke_pair):
    """One batched (B, k+1) verify == k+1 sequential paged_decode steps:
    every logit row and every arena byte, two rows at different positions
    (one crossing a page boundary mid-slab)."""
    model, params, _, _ = smoke_pair
    cfg = model.cfg
    plan = plan_attention(32, 4)
    _, bucket = plan.bucket_for(10)          # post-append worst case
    rng = np.random.RandomState(3)
    # row 0 at pos 7 (slab spans pages 2->5), row 1 at pos 2 (within page 3)
    kv0, _ = _prefilled_state(cfg, params, rng,
                              [([1, 2], 7), ([3], 2)], acc=bucket.acc)
    pt = jnp.asarray([[1, 2, 5], [3, 6, 0]], jnp.int32)
    positions = jnp.asarray([7, 2], jnp.int32)
    s_v = 3                                   # k = 2 drafts + last committed
    cand = jnp.asarray(rng.randint(0, cfg.vocab_size, (2, s_v)), jnp.int32)
    kw = dict(kv_fmt=FP8_152, acc=bucket.acc)

    logits_v, kv_v = lm.paged_verify(
        params, cand, kv0, pt, positions, positions + 1, cfg, **kw)
    assert logits_v.shape == (2, s_v, cfg.vocab_size)

    kv_seq = kv0
    for j in range(s_v):
        logits_j, kv_seq = lm.paged_decode(
            params, cand[:, j:j + 1], kv_seq, pt, positions + j,
            positions + 1 + j, cfg, **kw)
        np.testing.assert_array_equal(np.asarray(logits_v[:, j]),
                                      np.asarray(logits_j[:, 0]))
    for key in kv_v:
        np.testing.assert_array_equal(np.asarray(kv_v[key]),
                                      np.asarray(kv_seq[key]))


def test_rollback_arena_bitwise_never_appended(smoke_pair):
    """Speculative append + page-exact scrub == never appended: after
    truncate_pages the arena is bitwise the pre-verify arena, including
    the mid-page boundary slot and the freed page's scale exponents."""
    model, params, _, _ = smoke_pair
    cfg = model.cfg
    plan = plan_attention(32, 4)
    _, bucket = plan.bucket_for(10)
    rng = np.random.RandomState(4)
    kv0, _ = _prefilled_state(cfg, params, rng, [([1, 2], 7)], acc=bucket.acc)
    # append 3 tokens at pos 7..9: slot 3 of page 2, slots 0..1 of page 5
    pt = jnp.asarray([[1, 2, 5]], jnp.int32)
    cand = jnp.asarray(rng.randint(0, cfg.vocab_size, (1, 3)), jnp.int32)
    _, kv_app = lm.paged_verify(
        params, cand, kv0, pt, jnp.asarray([7], jnp.int32),
        jnp.asarray([8], jnp.int32), cfg, kv_fmt=FP8_152, acc=bucket.acc)
    changed = any(not np.array_equal(np.asarray(kv_app[k]),
                                     np.asarray(kv0[k])) for k in kv0)
    assert changed, "the verify append must actually touch the arena"
    # total rejection: keep 7 -> free page 5, scrub page 2 past slot 3
    kv_rb = truncate_pages(kv_app, jnp.asarray([5], jnp.int32),
                           jnp.int32(2), jnp.int32(3))
    for key in kv0:
        np.testing.assert_array_equal(np.asarray(kv_rb[key]),
                                      np.asarray(kv0[key]))


# --------------------------------------------------------------------------
# planner level: (bucket, k) certification
# --------------------------------------------------------------------------


def test_plan_verify_certifies_every_bucket():
    plan = plan_attention(64, 4)
    vp = plan_verify(plan, k=3)
    assert vp.s_v == 4 and vp.plan is plan
    # the verify bucket lookup is the base plan's (post-round worst case)
    for ctx in (1, 4, 5, 17, 64):
        assert vp.bucket_for(ctx) == plan.bucket_for(ctx)
    with pytest.raises(ValueError, match="k >= 1"):
        plan_verify(plan, k=0)


def test_plan_verify_rejects_doctored_buckets():
    """Certification failure is a refusal, never a silent widening: a
    bucket too small for the verify slab, or with a degraded e_acc, kills
    the whole verify plan."""
    plan = plan_attention(64, 4)
    # smallest bucket holds page_size=4 tokens: k=4 needs a 5-token slab
    with pytest.raises(ValueError, match="cannot hold"):
        plan_verify(plan, k=4)
    bad = dataclasses.replace(
        plan, buckets=(dataclasses.replace(plan.buckets[-1], e_acc=2),)
        + plan.buckets[1:])
    with pytest.raises(ValueError, match="e_acc"):
        plan_verify(bad, k=2)


# --------------------------------------------------------------------------
# engine level: spec streams == plain streams on the real model
# --------------------------------------------------------------------------

_ENG_KW = dict(n_pages=14, page_size=4, max_batch=3)


def _run(eng, prompts, gen):
    rids = [eng.submit(list(p), gen) for p in prompts]
    out = eng.run()
    eng.pool.check_invariants()
    return [tuple(out[r]) for r in rids]


def test_spec_engine_stream_matches_plain_greedy(smoke_pair):
    """The acceptance gate on the real smoke pair: spec-decoded streams
    (independent 0.5b draft, so rejections + rollbacks really happen)
    are bitwise the plain engine's greedy streams."""
    model, params, dmodel, dparams = smoke_pair
    rng = np.random.RandomState(11)
    prompts = [list(rng.randint(0, model.cfg.vocab_size, n))
               for n in (5, 9, 3)]
    plain = _run(ServeEngine(model, params, **_ENG_KW), prompts, 5)
    eng = SpecDecodeEngine(model, params, spec_k=2, draft_model=dmodel,
                           draft_params=dparams, **_ENG_KW)
    assert _run(eng, prompts, 5) == plain
    assert eng.spec_rounds > 0
    assert eng.draft_pool.free_pages == eng.draft_pool.n_pages - 1
    # an unrelated draft model accepts sometimes, not always
    assert 0.0 <= eng.acceptance_rate() < 1.0


def test_spec_engine_in_place_arena_matches_layer_slabs(smoke_pair,
                                                       monkeypatch):
    """Verify and rollback over the in-place arena: the spec engine's
    streams and both lanes' arena bytes are those it gives over per-layer
    slabs (``conftest.layer_sliced_arena``), with rejections, so rollbacks
    really scrub pages."""
    model, params, dmodel, dparams = smoke_pair
    rng = np.random.RandomState(14)
    prompts = [list(rng.randint(0, model.cfg.vocab_size, n))
               for n in (5, 9, 3)]

    def run():
        eng = SpecDecodeEngine(model, params, spec_k=2, draft_model=dmodel,
                               draft_params=dparams, **_ENG_KW)
        streams = _run(eng, prompts, 5)
        arenas = [{k: np.asarray(a) for k, a in ex.kv.items()}
                  for ex in (eng.executor, eng.draft_executor)]
        return streams, arenas, eng

    streams, arenas, eng = run()
    assert eng.spec_rollback_tokens > 0
    layer_sliced_arena(monkeypatch.setattr)
    want_streams, want_arenas, _ = run()
    assert streams == want_streams
    for arena, want in zip(arenas, want_arenas):
        for key in arena:
            np.testing.assert_array_equal(arena[key], want[key])


def test_spec_engine_all_accept_when_draft_is_target(smoke_pair):
    """Draft == target (same params, same arena discipline): every
    proposal is the target's own argmax, so acceptance is exactly 1.0 and
    rollbacks only trim the free bonus-token slot — the strongest
    end-to-end witness that both lanes' caches are bitwise aligned."""
    model, params, _, _ = smoke_pair
    rng = np.random.RandomState(12)
    prompts = [list(rng.randint(0, model.cfg.vocab_size, n)) for n in (6, 4)]
    plain = _run(ServeEngine(model, params, **_ENG_KW), prompts, 6)
    eng = SpecDecodeEngine(model, params, spec_k=2, draft_model=model,
                           draft_params=params, **_ENG_KW)
    assert _run(eng, prompts, 6) == plain
    assert eng.spec_rounds > 0 and eng.spec_proposed > 0
    assert eng.acceptance_rate() == 1.0


def test_spec_engine_zero_steady_state_compiles(smoke_pair):
    """A warm-started spec engine serves mixed traffic — spec rounds,
    rollbacks, draft primes, plain-lane fallback rows — with ZERO new
    traces on BOTH executors (the serve-spec CI bench gates this)."""
    model, params, dmodel, dparams = smoke_pair
    eng = SpecDecodeEngine(model, params, spec_k=2, draft_model=dmodel,
                           draft_params=dparams, warm_start=True,
                           prefill_chunk_tokens=4, **_ENG_KW)
    base = eng.compile_stats()
    assert base is not None and base["compiles"] > 0
    rng = np.random.RandomState(13)
    with eng.executor.compile_stats_scope() as d_t, \
            eng.draft_executor.compile_stats_scope() as d_d:
        for _ in range(2):
            for _ in range(3):
                n = int(rng.randint(3, 13))
                g = int(rng.randint(1, 6))   # gen=1 rides the plain lane
                eng.submit(list(rng.randint(1, model.cfg.vocab_size, n)), g)
            eng.run()
    assert eng.spec_rounds > 0 and eng.spec_rollback_tokens > 0
    for delta in (d_t, d_d):
        assert delta["compiles"] == 0, delta
        assert delta["misses"] == 0, delta
        assert delta["hits"] > 0, delta
