"""The extension path later PRs take: a new configuration, a new traffic
mix and a new per-layer metric reader, added as files beside the
existing ones and named in BENCHMARK.json, are found and run by name, and
no file that was there is edited."""

import hashlib
import json
import os

from conftest import run_tiny


def digest(root):
    out = {}
    for dirpath, _, files in os.walk(os.path.join(root, "benchmarks")):
        for f in files:
            p = os.path.join(dirpath, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_new_config_traffic_and_metric_by_name(root, monkeypatch):
    import harness

    data = os.path.join(root, "benchmarks", "chip")
    before = digest(root)
    with open(os.path.join(data, "configs", "tiny.train.json")) as f:
        cfg = json.load(f)
    cfg["num_hidden_layers"] = 1
    new = {
        "configs/tiny1.train.json": json.dumps(cfg),
        "traffic/tiny_train_b2.json": json.dumps(
            {"driver": "train", "why": "new mix", "batch": 2, "seq": 16,
             "zipf_exponent": {"dist": "constant", "value": 1.0,
                               "int": False}}),
        "limits/tiny1.b2.json": json.dumps(
            {"loss_gap": 0.05, "grad_gap": 0.3, "update_gap": 0.3}),
        "metrics/steps_seen.py": (
            '"""Tokens per traced step, from the counters."""\n\n\n'
            'def read(ctx):\n'
            '    c = ctx["counters"]\n'
            '    return float(c["batch"] * c["seq"])\n'),
    }
    for rel, text in new.items():
        with open(os.path.join(data, rel), "w") as f:
            f.write(text)
    bench_path = os.path.join(root, "BENCHMARK.json")
    with open(bench_path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny1.train", "source": "test",
                             "file": "benchmarks/chip/configs/"
                                     "tiny1.train.json",
                             "reduced": ["num_hidden_layers"],
                             "why": "test"})
    bench["workloads"].append({"name": "tiny1.b2", "config": "tiny1.train",
                               "traffic": "tiny_train_b2", "chips": 1,
                               "why": "test"})
    bench["end_to_end"][0]["workloads"].append("tiny1.b2")
    bench["per_layer"].append({"name": "steps_seen", "unit": "tokens",
                               "better": "higher", "source":
                               "program_counter", "layer": "train loop",
                               "moves": "train_tokens_per_s",
                               "workloads": ["tiny1.b2"]})
    with open(bench_path, "w") as f:
        json.dump(bench, f)

    cell = harness.Cell("tiny1.b2", root=root)
    assert cell.config["num_hidden_layers"] == 1
    assert [m["name"] for m in cell.per_layer] == ["steps_seen"]
    assert cell.reader("steps_seen").read(
        {"counters": {"batch": 2, "seq": 16}}) == 32.0
    r = run_tiny(root, "tiny1.b2")
    assert r["correct"], r["compared"]
    assert set(r["metrics"]) == {"train_tokens_per_s", "setup_s"}

    after = digest(root)
    for rel, h in before.items():
        assert after[rel] == h, f"{rel} was edited"
