"""The tensor-parallel serving driver (``drivers/serve_tp.py``) on a tiny
Qwen3-shaped configuration over four forced CPU devices: a cell added as
files beside the others and named in the test root's BENCHMARK.json runs
end to end, the program's sharded executor serves it, and the plain
reference finds it ``correct``."""

import json
import os
import subprocess
import sys

from conftest import CHIP, REPO

TINY = {
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 16, "num_key_value_heads": 4, "head_dim": 16,
    "attention_bias": False, "vocab_size": 256, "rms_norm_eps": 1e-06,
    "rope_theta": 1000000.0, "tie_word_embeddings": False,
    "program": {"arch": "qwen3-8b", "policy": "predicted", "chunk": 64,
                "kv_fmt": [5, 2], "page_size": 16, "n_pages": 129,
                "prefill_chunk": 32, "max_batch": 4, "shards": 4},
}
MIX = {"driver": "serve_tp", "why": "tiny closed loop", "loop": "closed",
       "clients": 4, "prompt": {"dist": "loguniform", "min": 8, "max": 48},
       "output": {"dist": "uniform", "min": 4, "max": 16},
       "check_requests": 2}
# limits from calibrate.py readings of this tiny cell on the CPU at 1 s
# over 10 seeds: sound runs up to 0.214 and 1.258 (a short request's
# quantisation error, drawn or not by timing); the int4 control 0.58-1.11
# and 1.83-3.30; row 0's token altered 1.20-2.04 and 3.78-4.80
LIMITS = {"mean_gap": 0.4, "token_gap": 1.6}


def add_cell(root: str) -> None:
    data = os.path.join(root, "benchmarks", "chip")
    files = {"configs/tiny3.serve.json": TINY,
             "traffic/tiny_closed_tp.json": MIX,
             "limits/tiny3.tp4.json": LIMITS}
    for rel, obj in files.items():
        with open(os.path.join(data, rel), "w") as f:
            json.dump(obj, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny3.serve", "source": "test",
                             "file": "benchmarks/chip/configs/"
                                     "tiny3.serve.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny3.tp4", "config": "tiny3.serve",
                               "traffic": "tiny_closed_tp", "chips": 4,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] in ("serve_tokens_per_s", "itl_p95_ms"):
            m["workloads"].append("tiny3.tp4")
    with open(path, "w") as f:
        json.dump(bench, f)


RUN = r'''
import json, sys, time
sys.path[:0] = [sys.argv[2], sys.argv[3]]
import harness
cell = harness.Cell("tiny3.tp4", root=sys.argv[1])
r = harness.run_cell(cell, seed=12345678901, seconds=1.0, trace=False,
                     process_start=time.perf_counter(), require_tpu=False,
                     log=lambda m: None)
print(json.dumps(r))
'''
# calibrate.py's readings of the cell: the int4 control and row 0's token
# altered in every decode call, each against the cell's limits
READINGS = r'''
import json, sys
sys.path[:0] = [sys.argv[2], sys.argv[3]]
import calibrate, harness
cell = harness.Cell("tiny3.tp4", root=sys.argv[1])
limits = dict(cell.limits)
out = {}
for mode in ("control", "token"):
    got = calibrate.readings(cell, mode, 12345678901, 1.0,
                             require_tpu=False)
    out[mode] = harness.judge([(k, got[k]) for k in limits], limits)[0]
    if mode == "control":
        out["program"] = harness.judge(
            [(k, got["program_" + k]) for k in limits], limits)[0]
print(json.dumps(out))
'''


def run_four(root: str, code: str = RUN) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, "-c", code, root, CHIP, os.path.join(REPO, "src")],
        capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_reference_scores_live_requests_by_context():
    import harness

    drv = harness.load_module(os.path.join(CHIP, "drivers", "serve_tp.py"),
                              "driver_serve_tp_sample")
    prompts = {0: [1] * 2000, 1: [1] * 900, 2: [1] * 150, 3: [1] * 130,
               4: [1] * 500}
    finished = {2: [5] * 40, 3: [5] * 30}
    live = {0: [5] * 300, 1: [5] * 310, 4: []}
    served, sample = drv.scored(prompts, finished, live, 3, 12345678901)
    # a live request's served prefix is scored; one that served nothing is
    # not; the longest context comes first though it served fewer tokens
    assert served == {0: [5] * 300, 1: [5] * 310, 2: [5] * 40, 3: [5] * 30}
    assert sample[0] == 0 and len(sample) == 3
    assert set(sample) <= set(served)


def test_tp_driver_serves_a_tiny_qwen3_correctly(root):
    add_cell(root)
    r = run_four(root)
    assert r["correct"], r["compared"]
    assert r["device"]["count"] == 4
    assert set(r["metrics"]) == {"serve_tokens_per_s", "itl_p95_ms",
                                 "setup_s"}
    assert r["metrics"]["serve_tokens_per_s"]["value"] > 0


def test_tp_control_and_altered_token_fail_the_limits(root):
    add_cell(root)
    got = run_four(root, READINGS)
    assert got == {"program": True, "control": False, "token": False}
