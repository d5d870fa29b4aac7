"""Unit tests for the unified observability primitives: the metrics
registry + exporters, the shared JSONL appender, the bounded ring buffer and
the latency percentile helper."""

from __future__ import annotations

import json

import pytest

from repro.obs import (
    MetricsRegistry,
    RingBuffer,
    jsonl_append,
    percentile,
    record_controller_events,
    set_registry,
)


# --------------------------------------------------------------------------
# ring buffer
# --------------------------------------------------------------------------


def test_ring_buffer_bounds_growth_and_counts_drops():
    rb = RingBuffer(3)
    for i in range(10):
        rb.append(i)
    assert list(rb) == [7, 8, 9]
    assert len(rb) == 3
    assert rb.dropped == 7
    assert rb[0] == 7 and rb[-1] == 9 and rb[1:] == [8, 9]
    assert bool(rb)
    rb.clear()
    assert not rb and len(rb) == 0


def test_ring_buffer_unbounded_and_invalid_capacity():
    rb = RingBuffer(None)
    rb.extend(range(10_000))
    assert len(rb) == 10_000 and rb.dropped == 0
    with pytest.raises(ValueError):
        RingBuffer(0)
    with pytest.raises(ValueError):
        RingBuffer(-1)


# --------------------------------------------------------------------------
# shared sink
# --------------------------------------------------------------------------


def test_jsonl_append_creates_dirs_and_appends(tmp_path):
    p = tmp_path / "a" / "b" / "log.jsonl"
    jsonl_append(str(p), [{"x": 1}])
    jsonl_append(str(p), [{"x": 2}, {"x": 3}])
    rows = [json.loads(line) for line in p.read_text().splitlines()]
    assert rows == [{"x": 1}, {"x": 2}, {"x": 3}]


# --------------------------------------------------------------------------
# registry
# --------------------------------------------------------------------------


def test_counter_gauge_histogram_basics():
    r = MetricsRegistry()
    c = r.counter("repro_t_total", "things", labels=("kind",))
    c.inc(kind="a")
    c.inc(2, kind="a")
    c.inc(kind="b")
    assert c.value(kind="a") == 3 and c.value(kind="b") == 1
    with pytest.raises(ValueError):
        c.inc(-1, kind="a")
    with pytest.raises(ValueError):
        c.inc(wrong_label="a")

    g = r.gauge("repro_t_gauge")
    g.set(7.5)
    assert g.value() == 7.5

    h = r.histogram("repro_t_seconds", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    s = h.summary()
    assert s["count"] == 3 and s["sum"] == pytest.approx(5.55)
    assert s["counts"] == [1, 1, 1]  # 0.1, 1.0, +Inf


def test_registry_get_or_create_and_mismatch():
    r = MetricsRegistry()
    c1 = r.counter("repro_x_total", labels=("a",))
    c2 = r.counter("repro_x_total", labels=("a",))
    assert c1 is c2
    with pytest.raises(ValueError):
        r.gauge("repro_x_total")  # kind mismatch
    with pytest.raises(ValueError):
        r.counter("repro_x_total", labels=("b",))  # label mismatch


def test_prometheus_exposition_format(tmp_path):
    r = MetricsRegistry(constant_labels={"shard": "2"})
    r.counter("repro_e_total", "events", labels=("kind",)).inc(3, kind="x")
    r.histogram("repro_lat_seconds", buckets=(1.0,)).observe(0.5)
    text = r.to_prometheus()
    assert "# TYPE repro_e_total counter" in text
    assert 'repro_e_total{kind="x",shard="2"} 3.0' in text
    assert 'repro_lat_seconds_bucket{le="1.0",shard="2"} 1' in text
    assert 'repro_lat_seconds_bucket{le="+Inf",shard="2"} 1' in text
    assert 'repro_lat_seconds_count{shard="2"} 1' in text
    out = tmp_path / "m.prom"
    r.export_prometheus(str(out))
    assert out.read_text() == text


def test_jsonl_export_round_trips(tmp_path):
    r = MetricsRegistry()
    r.counter("repro_a_total").inc(5)
    r.gauge("repro_b", labels=("k",)).set(1.5, k="v")
    p = tmp_path / "m.jsonl"
    assert r.export_jsonl(str(p)) == 2
    rows = [json.loads(line) for line in p.read_text().splitlines()]
    by_name = {row["metric"]: row for row in rows}
    assert by_name["repro_a_total"]["value"] == 5.0
    assert by_name["repro_b"]["labels"] == {"k": "v"}


def test_record_controller_events_maps_both_schemas():
    r = MetricsRegistry()
    # controller-style and serve-monitor-style events share the key subset
    record_controller_events(r, [
        {"gemm": "mlp_up", "role": "grad", "event": "bump", "m_acc": 9,
         "measured_vrr": 0.7, "log_v": 160.0, "swamp_rate": 0.3},
        {"gemm": "attn_decode", "role": "serve", "event": "ok", "m_acc": 7},
    ], area="ctl")
    assert r.counter("repro_ctl_events_total", labels=("gemm", "role", "event")
                     ).value(gemm="mlp_up", role="grad", event="bump") == 1
    assert r.gauge("repro_ctl_m_acc", labels=("gemm", "role")
                   ).value(gemm="attn_decode", role="serve") == 7.0
    assert r.gauge("repro_ctl_measured_vrr", labels=("gemm", "role")
                   ).value(gemm="mlp_up", role="grad") == 0.7


def test_collect_process_metrics_sweeps_counter_surfaces():
    from repro.obs import collect_process_metrics

    r = MetricsRegistry()
    collect_process_metrics(r)
    names = {s["metric"] for s in r.snapshot()}
    # the serve compile cache aggregate is always present (entries >= 0)
    assert "repro_serve_compile_cache" in names


def test_process_default_registry_swap():
    from repro.obs import get_registry

    fresh = MetricsRegistry()
    set_registry(fresh)
    try:
        assert get_registry() is fresh
    finally:
        set_registry(None)


# --------------------------------------------------------------------------
# percentile
# --------------------------------------------------------------------------


def test_percentile_nearest_rank_and_none_filtering():
    assert percentile([], 50) is None
    assert percentile([None, None], 99) is None
    vals = [5.0, 1.0, None, 3.0]
    assert percentile(vals, 0) == 1.0
    assert percentile(vals, 50) == 3.0
    assert percentile(vals, 100) == 5.0


def test_record_spec_events_maps_spec_round_schema():
    """`record_spec_events` mirrors SpecDecodeEngine `spec_round` events
    into `repro_serve_spec_*` counters + the rollback-depth histogram,
    skipping non-spec events (the engine's ring buffer interleaves
    preempt/monitor records with spec rounds)."""
    from repro.obs import record_spec_events

    r = MetricsRegistry()
    record_spec_events(r, [
        {"step": 3, "event": "spec_round", "role": "serve", "rid": 0,
         "k": 4, "proposed": 4, "accepted": 4, "emitted": 5,
         "rollback_depth": 0, "ctx": 17},
        {"step": 4, "event": "spec_round", "role": "serve", "rid": 1,
         "k": 4, "proposed": 4, "accepted": 1, "emitted": 2,
         "rollback_depth": 3, "ctx": 9},
        {"step": 4, "event": "preempt", "rid": 2},   # skipped: not a round
    ])
    assert r.counter("repro_serve_spec_rounds_total").value() == 2
    assert r.counter("repro_serve_spec_proposed_tokens_total").value() == 8
    assert r.counter("repro_serve_spec_accepted_tokens_total").value() == 5
    assert r.counter("repro_serve_spec_emitted_tokens_total").value() == 7
    assert r.counter("repro_serve_spec_rollback_tokens_total").value() == 3
    h = r.histogram("repro_serve_spec_rollback_depth",
                    buckets=(0, 1, 2, 4, 8, 16, float("inf"))).summary()
    assert h["count"] == 2 and h["sum"] == 3.0
    # depth 0 (all-accept) and depth 3 land in the right buckets
    assert h["counts"][0] == 1 and h["counts"][3] == 1
    # the textfile exporter carries every spec series
    text = r.to_prometheus()
    for name in ("repro_serve_spec_rounds_total",
                 "repro_serve_spec_accepted_tokens_total",
                 "repro_serve_spec_rollback_depth_bucket"):
        assert name in text, name
