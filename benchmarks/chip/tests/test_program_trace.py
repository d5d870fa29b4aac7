"""The readers of the program's own spans and module names, on a hand-built
trace with known answers, on profiles recorded here on the CPU, and on a
second of the serving cell recorded on the chip (``testdata/``)."""

import os

import pytest

import harness
import program_trace as PT
import trace_reduce as TR

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
READERS = ("engine_self_ms.decode", "executor_host_ms.decode",
           "decode_device_ms", "prefill_device_ms", "idle_engine.decode",
           "idle_executor.decode")
DEV = "/device:TPU:0"

# one tick: a prefill slab's program (150..290) then a decode call's
# (310..860); the host's innermost span over the three idle gaps is, in
# turn, none / the engine / the executor
HAND = {
    "devices": {DEV: [["%fusion.1 = f32[8]{0} fusion()", 150, 290],
                      ["%custom-call.2 = f32[8]{0} custom-call()", 310,
                       860]]},
    "host": [["bench.serve.step", 0, 1000]],
    "window": [0, 1000],
    "program": {
        "spans": [["serve.step", 50, 950, {}],
                  ["serve.admit", 60, 80, {}],
                  ["serve.decode_step", 100, 900, {"rows": 2}],
                  ["executor.decode", 120, 880, {"rows": 2}],
                  ["executor.decode.wait", 300, 880, {}]],
        "modules": {DEV: [["jit_serve_prefill_final(7)", 150, 290],
                          ["jit_serve_decode(9)", 310, 860]]},
    },
}


def reader(name):
    return harness.load_module(os.path.join(CHIP, "metrics", name + ".py"),
                               "test_metric_" + name.replace(".", "_"))


def read_all(ctx) -> dict:
    ctx = dict(ctx, log=lambda m: None)
    return {name: reader(name).read(ctx) for name in READERS}


def device_idle(flat) -> float:
    return reader("device_idle.decode").read({"trace": flat})


def test_idle_split_of_the_hand_trace():
    ctx = {"trace": HAND, "program": HAND["program"]}
    assert PT.idle_split(ctx) == {"engine": 140, "executor": 70,
                                  "outside": 100, "window": 1000}
    got = read_all(ctx)
    assert got["idle_engine.decode"] == pytest.approx(14.0)
    assert got["idle_executor.decode"] == pytest.approx(7.0)
    assert device_idle(HAND) == pytest.approx(31.0)


def test_self_times_and_modules_of_the_hand_trace():
    spans = HAND["program"]["spans"]
    assert PT.self_ns(spans, "serve.step", "executor.") == [140]
    assert PT.self_ns(spans, "executor.decode",
                      "executor.decode.wait") == [180]
    got = read_all({"trace": HAND, "program": HAND["program"]})
    assert got["engine_self_ms.decode"] == pytest.approx(140e-6)
    assert got["executor_host_ms.decode"] == pytest.approx(180e-6)
    assert got["decode_device_ms"] == pytest.approx(550e-6)
    assert got["prefill_device_ms"] == pytest.approx(140e-6)
    assert PT.innermost(spans)[:3] == [(50, 60, "serve.step"),
                                       (60, 80, "serve.admit"),
                                       (80, 100, "serve.step")]


def test_a_program_without_spans_or_names_reads_nothing():
    """An older program: the same device work under one module name and
    no program spans.  Every reader returns None, none raises."""
    old = {"spans": [], "modules": {DEV: [["jit_counted(3)", 150, 290],
                                          ["jit_counted(3)", 310, 860]]}}
    assert read_all({"trace": HAND, "program": old}) == dict.fromkeys(
        READERS)
    assert read_all({"trace": dict(HAND, window=None)}) == dict.fromkeys(
        READERS)


def record(root, cell, marks):
    """A CPU profile under ``root/.bench_trace/<cell>``: each mark is one
    ``bench.serve.step`` annotation around a ``serve.step`` scope."""
    import jax

    from repro.obs.trace import span

    trace_dir = os.path.join(root, ".bench_trace", cell)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(trace_dir, profiler_options=opts):
        for rows in marks:
            with jax.profiler.TraceAnnotation("bench.serve.step"):
                with span(None, "serve.step"):
                    with span(None, "executor.decode", rows=rows):
                        pass
    return TR.flatten(trace_dir)


def test_the_traced_runs_profile_is_found_by_its_annotations(tmp_path,
                                                              monkeypatch):
    root = str(tmp_path)
    monkeypatch.setattr(PT, "ROOT", root)
    other = record(root, "b", [5])
    flat = record(root, "a", [2, 3])
    assert len(PT.locate()) == 2
    prog = PT.program({"trace": flat})
    assert [n for n, *_ in prog["spans"]] == ["serve.step",
                                              "executor.decode"] * 2
    assert [st for n, _, _, st in prog["spans"]
            if n == "executor.decode"] == [{"rows": 2}, {"rows": 3}]
    lo, hi = flat["window"]
    assert all(lo <= s < e <= hi for _, s, e, _ in prog["spans"])
    assert PT.program({"trace": other})["spans"][1][3] == {"rows": 5}
    stranger = dict(flat, host=flat["host"][:1])
    assert PT.program({"trace": stranger}) is None


RECORDED = os.path.join(CHIP, "testdata", "trace_serve_spans_v5e.json.gz")


def test_recorded_serve_second():
    """A second of ``serve.qwen2-1.5b.decode64`` recorded on the chip: all
    six readers read, and the idle split adds up to the device's idle
    share."""
    flat = TR.load(RECORDED)
    ctx = {"trace": {k: flat[k] for k in ("devices", "host", "window")},
           "program": flat["program"]}
    got = read_all(ctx)
    assert all(v is not None and v > 0 for v in got.values()), got
    split = PT.idle_split(ctx)
    parts = 100.0 * (split["engine"] + split["executor"]
                     + split["outside"]) / split["window"]
    assert parts == pytest.approx(device_idle(ctx["trace"]), abs=0.01)
    assert got["decode_device_ms"] < 1e3 * (flat["window"][1]
                                            - flat["window"][0]) / 1e9
    ticks = PT.named(flat["program"]["spans"], "serve.step")
    assert len(PT.modules(flat["program"], PT.DECODE_MODULE)) == len(ticks)
