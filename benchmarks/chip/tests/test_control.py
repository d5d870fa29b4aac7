"""The control, at a size a CPU test run holds: the reference computed at
int4 operands (the precision below the configuration's fp8) in the
program's place must come out as not correct against the cell's limits."""

import harness
import calibrate


def test_training_control_fails_the_limits(root):
    cell = harness.Cell("tiny.train", root=root)
    limits = dict(cell.limits)
    got = calibrate.readings(cell, "control", 12345678901, 1.0,
                             require_tpu=False)
    ok, rows = harness.judge(sorted((k, got[k]) for k in limits), limits)
    assert not ok, rows


def test_serving_control_fails_the_limits(root):
    cell = harness.Cell("tiny.decode", root=root)
    limits = dict(cell.limits)
    got = calibrate.readings(cell, "control", 12345678901, 1.0,
                             require_tpu=False)
    for name in ("token_gap", "mean_gap"):
        assert got["program_" + name] <= limits[name], got
    ok, rows = harness.judge([(k, got[k]) for k in limits], limits)
    assert not ok, rows
