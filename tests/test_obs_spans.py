"""Span-tree invariants for the request-lifecycle tracer, pinned under the
deterministic scheduler sim (no model, no device):

* every emitted token is attributable to exactly ONE request root span
  (token events on roots == finished generations, per request);
* spans survive preemption/swap-out/swap-in/restore without orphans —
  ``span_forest`` raises on any dangling parent, every ``swapped`` child
  closes by drain, and preemption counts match ``swapped`` spans;
* with the tracer on the sim's virtual clock, the span tree is a pure
  function of (trace, seed): two replays are byte-identical;
* observability OFF is bit-identical to the instrumented engine: same
  token streams, same event log, same scheduling metrics — the guarded
  blocks add behavior, never change it;
* each tick is one ``serve.step`` tree, and the same scopes appear in a
  ``jax.profiler`` capture of a real engine (CPU), nested as in the tracer
  and as long, beside programs named ``jit_serve_*``.
"""

from __future__ import annotations

import glob
import os

import pytest

from repro.obs import MetricsRegistry, Tracer, VirtualClock, span, span_forest
from repro.serve.scheduler import ServeEngine
from repro.serve.sim import (
    SimExecutor,
    adversarial_trace,
    poisson_burst_trace,
    replay_trace,
)

BASE_SEED = int(os.environ.get("REPRO_SIM_SEED", "20260730"))
PAGE = 4
# the near-capacity regime from test_serve_sim: guaranteed preemptions
TIGHT = dict(n_pages=12, max_batch=4)
TIGHT_TRAFFIC = dict(n_requests=12, prompt_range=(2, 24), gen_range=(1, 12))


def make_engine(*, tracer=None, metrics=None, n_pages=12, max_batch=4, **kw):
    ex = SimExecutor(n_pages=n_pages, page_size=PAGE, vocab_size=211)
    eng = ServeEngine(None, None, n_pages=n_pages, page_size=PAGE,
                      max_batch=max_batch, executor=ex, tracer=tracer,
                      metrics=metrics, **kw)
    return eng, ex


def traced_replay(seed, *, chunk=PAGE, traffic=TIGHT_TRAFFIC, pool=TIGHT):
    tracer = Tracer(clock=VirtualClock())
    eng, ex = make_engine(tracer=tracer, prefill_chunk_tokens=chunk, **pool)
    trace = poisson_burst_trace(seed, max_request_tokens=eng.tokens_capacity,
                                **traffic)
    m = replay_trace(eng, trace)
    return eng, tracer, m


# --------------------------------------------------------------------------
# token attribution + orphan-free trees, fuzzed
# --------------------------------------------------------------------------


def check_span_invariants(eng, tracer, *, ctx=""):
    spans = tracer.to_dicts()
    forest = span_forest(spans)  # raises on any dangling parent_id
    roots = {s["trace_id"]: s for s in spans if s["name"] == "request"}
    # one root per submitted request, all closed after drain
    assert set(roots) == set(eng.finished), ctx
    for rid, root in roots.items():
        assert root["t_end"] is not None, f"{ctx}: rid {rid} root left open"
        toks = [e for e in root["events"] if e["name"] == "token"]
        assert len(toks) == len(eng.finished[rid]), (
            f"{ctx}: rid {rid} has {len(toks)} token events but "
            f"{len(eng.finished[rid])} generated tokens — a token is not "
            "attributable to exactly one request")
    # token events live ONLY on request roots: global count matches too
    total = sum(len([e for e in s["events"] if e["name"] == "token"])
                for s in spans)
    assert total == sum(len(v) for v in eng.finished.values()), ctx
    # lifecycle children carry their request's trace_id and close by drain
    swapped = [s for s in spans if s["name"] == "swapped"]
    for s in spans:
        if s["name"] in ("queued", "swapped", "serve.prefill_slab"):
            assert s["parent_id"] is not None and s["trace_id"] in roots, (
                f"{ctx}: orphan {s['name']} span")
            assert s["t_end"] is not None, (
                f"{ctx}: {s['name']} span never closed across "
                "preempt/swap/restore")
    assert len(swapped) == eng.preemptions, (
        f"{ctx}: {eng.preemptions} preemptions but {len(swapped)} swapped "
        "spans")
    assert all(s["t_end"] is None for s in spans) is False or not spans
    return spans


def test_token_attribution_and_no_orphans_fuzz():
    preempts = 0
    for i in range(12):
        for chunk in (None, PAGE, 2 * PAGE):
            seed = BASE_SEED + 7000 + i
            eng, tracer, m = traced_replay(seed, chunk=chunk)
            check_span_invariants(eng, tracer,
                                  ctx=f"seed {seed} chunk {chunk}")
            preempts += m["preemptions"]
    assert preempts > 0, ("the fuzz never preempted — swapped-span "
                          "invariants were not exercised")


def test_spans_survive_forced_preemption_of_oldest():
    """The engine's own victim policy never picks the oldest resident;
    forcing it through the public ``preempt`` must still produce a closed
    ``swapped`` span and exact token attribution."""
    tracer = Tracer(clock=VirtualClock())
    eng, ex = make_engine(tracer=tracer, n_pages=16, max_batch=4,
                          prefill_chunk_tokens=PAGE)
    for rid in range(3):
        eng.submit([1] * 10, 6)
    for _ in range(6):
        eng.step()
    oldest = min(eng.active)
    eng.preempt(oldest)
    eng.run()
    spans = check_span_invariants(eng, tracer, ctx="forced-oldest")
    swapped = [s for s in spans if s["name"] == "swapped"
               and s["trace_id"] == oldest]
    assert swapped and swapped[0]["t_end"] is not None


def test_adversarial_traces_keep_invariants():
    for kind in ("all_long", "all_short", "long_then_short",
                 "short_then_long"):
        tracer = Tracer(clock=VirtualClock())
        eng, ex = make_engine(tracer=tracer, n_pages=17, max_batch=4,
                              prefill_chunk_tokens=PAGE)
        trace = adversarial_trace(kind, n_requests=6,
                                  capacity_tokens=eng.tokens_capacity)
        replay_trace(eng, trace)
        check_span_invariants(eng, tracer, ctx=kind)


# --------------------------------------------------------------------------
# determinism: the span tree is a pure function of (trace, seed)
# --------------------------------------------------------------------------


def test_span_tree_is_schedule_deterministic():
    seed = BASE_SEED + 42
    _, tr_a, _ = traced_replay(seed)
    _, tr_b, _ = traced_replay(seed)
    a, b = tr_a.to_dicts(), tr_b.to_dicts()
    assert a == b, "same trace + seed produced different span trees"
    # virtual-clock timestamps are tick numbers, not wall time
    assert all(float(s["t_start"]).is_integer() for s in a)


# --------------------------------------------------------------------------
# obs-off bit-parity: instrumentation adds, never changes
# --------------------------------------------------------------------------


def test_obs_off_engine_is_bit_identical_to_instrumented():
    seed = BASE_SEED + 99
    for chunk in (None, PAGE):
        tracer = Tracer(clock=VirtualClock())
        reg = MetricsRegistry()
        eng_on, _ = make_engine(tracer=tracer, metrics=reg,
                                prefill_chunk_tokens=chunk, **TIGHT)
        eng_off, _ = make_engine(prefill_chunk_tokens=chunk, **TIGHT)
        trace = poisson_burst_trace(
            seed, max_request_tokens=eng_on.tokens_capacity, **TIGHT_TRAFFIC)
        m_on = replay_trace(eng_on, trace)
        m_off = replay_trace(eng_off, trace)
        assert eng_on.finished == eng_off.finished
        assert list(eng_on.events) == list(eng_off.events)
        for k in ("steps", "decoded_tokens", "prefill_slabs", "preemptions",
                  "restores", "max_concurrent"):
            assert m_on[k] == m_off[k], k
        # and the uninstrumented engine carries zero tracing state
        assert eng_off.tracer is None and not eng_off._spans


def test_metrics_counters_match_engine_counters():
    seed = BASE_SEED + 123
    reg = MetricsRegistry()
    eng, _ = make_engine(metrics=reg, prefill_chunk_tokens=PAGE, **TIGHT)
    trace = poisson_burst_trace(
        seed, max_request_tokens=eng.tokens_capacity, **TIGHT_TRAFFIC)
    replay_trace(eng, trace)
    assert reg.counter("repro_serve_preemptions_total").value() \
        == eng.preemptions
    assert reg.counter("repro_serve_restores_total").value() == eng.restores
    assert reg.counter("repro_serve_prefill_slabs_total").value() \
        == eng.prefill_slabs
    assert reg.counter("repro_serve_tokens_total").value() \
        == sum(len(v) for v in eng.finished.values())
    assert reg.counter("repro_serve_requests_finished_total").value() \
        == len(eng.finished)
    assert reg.gauge("repro_serve_free_pages").value() \
        == eng.pool.free_pages


def test_events_ring_buffer_caps_engine_event_growth():
    eng, _ = make_engine(events_capacity=5, prefill_chunk_tokens=PAGE,
                         **TIGHT)
    trace = poisson_burst_trace(
        BASE_SEED + 7, max_request_tokens=eng.tokens_capacity,
        **TIGHT_TRAFFIC)
    replay_trace(eng, trace)
    assert len(eng.events) <= 5
    total = len(eng.events) + eng.events.dropped
    assert total == eng.preemptions + eng.restores


# --------------------------------------------------------------------------
# TPOT under multi-token decode steps (speculative rounds)
# --------------------------------------------------------------------------


def test_tpot_from_token_events_not_step_count():
    """A speculative round commits several tokens at ONE timestamp, so a
    request can finish in far fewer decode steps than tokens.  TPOT must
    be the mean inter-token gap of the event stream — here 9 tokens land
    across 3 verify steps at ticks 1/3/5, so tpot == (5-1)/8 == 0.5; a
    step-count derivation (span / steps) would report (5-1)/2 == 2.0 and
    overstate the per-token latency by the acceptance factor."""
    from repro.obs import request_latencies

    clock = VirtualClock()
    tr = Tracer(clock=clock)
    root = tr.start("request", trace_id=7)
    for tick, burst in ((1, 3), (3, 2), (5, 4)):
        clock.set(tick)
        for _ in range(burst):
            tr.event(root, "token")
    tr.end(root)
    (lat,) = request_latencies(tr.spans)
    assert lat["tokens"] == 9
    assert lat["ttft"] == 1.0
    assert lat["tpot"] == 0.5
    assert lat["tpot"] != (5 - 1) / 2  # the per-step number is wrong


def test_tpot_sorts_reordered_token_events():
    """Merged span streams (per-shard tracers, concatenated JSONL) can
    deliver token events out of time order; the derivation sorts before
    differencing, so gaps can never go negative."""
    from repro.obs import request_latencies

    span = {"span_id": 1, "name": "request", "trace_id": 3,
            "parent_id": None, "t_start": 0.0, "t_end": 9.0, "attrs": {},
            "events": [{"name": "token", "t": t}
                       for t in (5.0, 1.0, 3.0, 9.0, 7.0)]}
    (lat,) = request_latencies([span])
    assert lat["ttft"] == 1.0
    assert lat["tpot"] == 2.0


# --------------------------------------------------------------------------
# per-tick scopes: the span helper, the sim engine's tree, the profile
# --------------------------------------------------------------------------


def test_span_helper_nests_and_takes_explicit_parents():
    assert span(None, "x").__enter__() is None
    tr = Tracer(clock=VirtualClock())
    root = tr.start("request", trace_id=5)
    with span(tr, "serve.step") as step:
        with span(tr, "serve.decode_step", attrs={"rids": [5]},
                  rows=1) as dec:
            with span(tr, "executor.decode", rows=1) as ex:
                pass
        with span(tr, "serve.prefill_slab", parent=root, trace_id=5,
                  tokens=4, final=True) as slab:
            with span(tr, "executor.prefill", tokens=4) as exp:
                pass
    assert step.parent_id is None and not step.open
    assert dec.parent_id == step.span_id
    assert dec.attrs == {"rids": [5], "rows": 1}
    assert ex.parent_id == dec.span_id
    assert slab.parent_id == root.span_id and slab.trace_id == 5
    assert exp.parent_id == slab.span_id and exp.trace_id == 5
    assert not tr._open
    span_forest(tr.spans)


def tick_trees(spans):
    """{serve.step span id: [(child name, attrs), ...]} in start order."""
    by_parent = {}
    for sp in spans:
        by_parent.setdefault(sp["parent_id"], []).append(sp)
    out = {}
    for step in (sp for sp in spans if sp["name"] == "serve.step"):
        assert step["parent_id"] is None and step["trace_id"] is None
        out[step["span_id"]] = [
            (c["name"], c["attrs"]) for c in by_parent.get(
                step["span_id"], [])]
    return out


def test_tick_span_tree_under_virtual_clock():
    """Each engine tick is one ``serve.step`` root holding ``serve.admit``
    and, when any row decodes, ``serve.decode_step`` with its ``rows``;
    ``serve.prefill_slab`` stays a child of its request, carrying
    ``tokens`` and ``final``."""
    eng, tracer, _ = traced_replay(BASE_SEED + 5)
    spans = tracer.to_dicts()
    trees = tick_trees(spans)
    assert len(trees) == eng.steps
    decodes = 0
    for kids in trees.values():
        names = [n for n, _ in kids]
        assert names[0] == "serve.admit"
        assert set(names) <= {"serve.admit", "serve.decode_step"}
        for name, attrs in kids:
            if name == "serve.decode_step":
                assert attrs["rows"] == len(attrs["rids"]) >= 1
                decodes += 1
    assert decodes == sum(1 for s in spans
                          if s["name"] == "serve.decode_step")
    slabs = [s for s in spans if s["name"] == "serve.prefill_slab"]
    assert len(slabs) == eng.prefill_slabs
    roots = {s["span_id"]: s for s in spans if s["name"] == "request"}
    for s in slabs:
        assert roots[s["parent_id"]]["trace_id"] == s["trace_id"]
        assert s["attrs"]["tokens"] == s["attrs"]["t1"] - s["attrs"]["t0"]
    assert sum(s["attrs"]["final"] for s in slabs) == len(eng.finished)
    assert sum(s["attrs"]["rows"] for s in spans
               if s["name"] == "serve.decode_step") == eng.decoded_tokens


def test_tick_span_trees_replay_identically():
    seed = BASE_SEED + 6
    trees = [tick_trees(traced_replay(seed)[1].to_dicts())
             for _ in range(2)]
    assert trees[0] == trees[1] and trees[0]


SCOPES = ("serve.step", "serve.admit", "serve.decode_step",
          "serve.prefill_slab", "serve.monitor", "executor.decode",
          "executor.decode.wait", "executor.prefill",
          "executor.prefill.wait")


def capture(model, params, out):
    """A tiny real engine with a tracer and one without, run under one
    ``jax.profiler`` capture into ``out``: (the profile's host events by
    name, in start order; the tracer; the two engines)."""
    import gc

    import jax
    import numpy as np
    from jax.profiler import ProfileData

    kw = dict(n_pages=12, page_size=4, max_batch=3, prefill_chunk_tokens=4,
              monitor_cadence=2, warm_start=True)
    tracer = Tracer()
    eng_on = ServeEngine(model, params, tracer=tracer, **kw)
    eng_off = ServeEngine(model, params, **kw)
    rng = np.random.RandomState(3)
    reqs = [(list(rng.randint(1, model.cfg.vocab_size, n)), g)
            for n, g in ((6, 3), (9, 2), (3, 4))]
    for eng in (eng_on, eng_off):
        for prompt, g in reqs:
            eng.submit(prompt, g)
    # the python tracer would put its own hooks between each scope and
    # its span; the scopes are TraceMe events, which it does not need
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    gc.disable()  # no collection pause inside a scope being compared
    try:
        with jax.profiler.trace(out, profiler_options=opts):
            eng_on.run()
            eng_off.run()
    finally:
        gc.enable()
    (path,) = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    events = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("serve.", "executor.")):
                    events.setdefault(e.name, []).append(
                        (int(e.start_ns), int(e.end_ns),
                         {k: v for k, v in e.stats}))
    for evs in events.values():
        evs.sort()
    return events, tracer, eng_on, eng_off


def mismatched(events, tracer, tol_s=50e-6) -> list:
    """Scopes whose tracer span and profile event differ in duration by
    more than ``tol_s``: [(name, difference in s)].  The engine with a
    tracer ran first, so its scopes lead the profile's."""
    out = []
    for name in SCOPES:
        mine = [s for s in tracer.spans if s.name == name]
        for sp, (a, b, _) in zip(mine, events.get(name, [])):
            d = (b - a) / 1e9 - sp.duration
            if abs(d) >= tol_s:
                out.append((name, d))
    return out


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """``capture`` on the CPU.  A busy host can deschedule the thread
    between a scope's profiler timestamp and its tracer timestamp, which
    no instrumentation controls, so a capture with such a gap is taken
    again, at most three times in all."""
    import jax

    from repro.configs import get_smoke_config
    from repro.models.api import get_model

    model = get_model(get_smoke_config("qwen2-1.5b"))
    params = model.init_params(jax.random.PRNGKey(0))
    for _ in range(3):
        got = capture(model, params,
                      str(tmp_path_factory.mktemp("profile")))
        if not mismatched(*got[:2]):
            break
    return got


def test_engine_and_executor_spans_in_the_profile(profiled):
    """``serve.step`` ⊃ ``serve.decode_step`` ⊃ ``executor.decode`` ⊃
    ``executor.decode.wait`` in the profile's host plane, each profile
    scope as long as its tracer span to within 50 µs."""
    events, tracer, eng_on, _ = profiled
    spans = [s for s in tracer.spans if s.name.startswith(
        ("serve.", "executor."))]
    mine = {}
    for s in spans:
        mine.setdefault(s.name, []).append(s)
    for name in SCOPES:
        assert mine.get(name) and len(events[name]) >= len(mine[name]), name
    assert mismatched(events, tracer) == []
    for name, outer in (("serve.decode_step", "serve.step"),
                        ("executor.decode", "serve.decode_step"),
                        ("executor.decode.wait", "executor.decode"),
                        ("executor.prefill", "serve.prefill_slab"),
                        ("serve.prefill_slab", "serve.step"),
                        ("serve.monitor", "serve.step")):
        for a, b, _ in events[name]:
            assert any(s <= a and b <= e for s, e, _ in events[outer]), (
                name, outer)
        for sp in mine[name]:
            if name != "serve.prefill_slab":  # its parent is the request
                parent = next(p for p in spans
                              if p.span_id == sp.parent_id)
                assert parent.name == outer
    rows = [st["rows"] for _, _, st in events["serve.decode_step"]]
    assert rows[:len(mine["serve.decode_step"])] == [
        s.attrs["rows"] for s in mine["serve.decode_step"]]
    assert sum(st["final"] for _, _, st in
               events["serve.prefill_slab"]) == 2 * len(eng_on.finished)


def test_engine_without_tracer_records_nothing(profiled):
    """The engine without a tracer writes its scopes into the profile
    (twice the tracer's ticks are there) and keeps no span state."""
    events, tracer, eng_on, eng_off = profiled
    assert eng_off.tracer is None and eng_off.executor.tracer is None
    assert not eng_off._spans
    assert eng_on.executor.tracer is tracer
    assert len(events["serve.step"]) == eng_on.steps + eng_off.steps
    assert eng_off.finished == eng_on.finished


def test_executor_programs_are_named(profiled):
    """Every executor program lowers to a module named after its kind, so
    the profile's ``XLA Modules`` line tells decode from prefill slabs."""
    import jax.numpy as jnp

    _, _, eng, _ = profiled
    ex, b = eng.executor, eng.plan.buckets[0]
    w, mb = b.max_pages(eng.page_size), ex.max_batch
    dec = ex._decode_fn(b.acc).lower(
        ex.params, jnp.zeros((mb, 1), jnp.int32), ex.kv,
        jnp.zeros((mb, w), jnp.int32), jnp.zeros((mb,), jnp.int32),
        jnp.zeros((mb,), jnp.int32))
    assert "module @jit_serve_decode " in dec.as_text()
    call = eng.plan.kernel_call(0, h=eng.cfg.n_heads, dh=eng.cfg.head_dim,
                                kv_fmt=eng.kv_fmt, slab_tokens=4)
    for final, module in ((True, "jit_serve_prefill_final"),
                          (False, "jit_serve_prefill")):
        low = ex._prefill_fn(b.acc, final, call).lower(
            ex.params, jnp.zeros((1, 4), jnp.int32), ex.kv,
            jnp.zeros((w,), jnp.int32), jnp.zeros((1,), jnp.int32),
            jnp.int32(0), jnp.int32(4))
        assert f"module @{module} " in low.as_text()
