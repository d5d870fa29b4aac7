"""The readers of the tensor-parallel cell's metrics on a hand-built
four-chip trace with known answers: collectives that overlap other ops
and collectives the device waits for, a scan's ``while`` around them, and
chips of different idle shares."""

import os

import pytest

import harness
import kernels as K
import tpwork as TP
import work as W

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEVS = [f"/device:TPU:{i}" for i in range(4)]

# chip 0, window 0..1000 ns: compute 100..300 and 500..600 inside a scan's
# while (90..700); collectives 250..350 (exposed 300..350), 400..450
# (exposed whole) and 580..620 (exposed 600..620): 190 ns of collectives,
# 120 of them exposed
CHIP0 = [["%while.3 = (s32[]) while()", 90, 700],
         ["%fusion.1 = bf16[8]{0} fusion()", 100, 300],
         ["%all-gather-start.1 = bf16[8]{0} all-gather-start()", 250, 350],
         ["%all-reduce.2 = f32[8]{0} all-reduce()", 400, 450],
         ["%custom-call.4 = f32[8]{0} custom-call()", 500, 600],
         ["%all-gather-done.5 = bf16[32]{0} all-gather-done()", 580, 620]]
HAND = {
    "devices": {DEVS[0]: CHIP0,
                DEVS[1]: [["%fusion.1 = f32[8]{0} fusion()", 0, 500]],
                DEVS[2]: [["%fusion.1 = f32[8]{0} fusion()", 0, 1000]],
                DEVS[3]: [["%fusion.1 = f32[8]{0} fusion()", 500, 700]]},
    "host": [["bench.serve.step", 0, 1000]],
    "window": [0, 1000],
}
PROGRAM = {"spans": [["executor.decode", 150, 720,
                      {"rows": 4, "shards": 4, "collective_bytes": 1900}]],
           "modules": {DEVS[0]: [["jit_serve_decode(9)", 200, 700]]}}
CFG = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
       "num_attention_heads": 16, "num_key_value_heads": 4, "head_dim": 16,
       "vocab_size": 256, "tie_word_embeddings": False,
       "program": {"shards": 4}}


def read(name, ctx):
    mod = harness.load_module(os.path.join(CHIP, "metrics", name + ".py"),
                              "test_tp_" + name.replace(".", "_"))
    return mod.read(dict(ctx, log=lambda m: None))


def ctx():
    return {"trace": HAND, "program": PROGRAM, "config": CFG}


def test_collective_split_by_hand():
    assert TP.collective_split(CHIP0, (0, 1000)) == {
        "collective": 190, "exposed": 120, "ops": 3}
    assert not TP.is_collective("%fusion.7 = f32[] fusion()")
    # an operand that is a collective's result is not one
    assert not TP.is_collective("%fusion.8 = f32[32]{0} fusion(%p.1, "
                                "%all-gather.25)")
    # XLA names some after the JAX primitive: found by the HLO opcode
    assert TP.is_collective("%pmax.43 = f32[32,32]{1,0:T(8,128)S(1)} "
                            "all-reduce(%dynamic_update_slice.785)")
    assert TP.is_collective("%all-reduce.10 = (f32[32]{0}, f32[32]{0}) "
                            "all-reduce(%fusion.828, %abs_reduce_fusion.2)")
    assert TP.is_collective("%reduce-scatter.1 = f32[2] reduce-scatter()")
    assert TP.is_collective("%collective-permute-done = f32[2] "
                            "collective-permute-done()")


def test_readers_of_the_hand_trace():
    assert read("collective_exposed.tp4", ctx()) == pytest.approx(12.0)
    assert read("collective_ms.tp4", ctx()) == pytest.approx(190e-6)
    assert read("decode_device_ms.tp4", ctx()) == pytest.approx(500e-6)
    # chips idle 39% (busy is the union of the ops, the while's span
    # included), 50%, 0% and 80% of the window
    assert read("device_idle.tp4", ctx()) == pytest.approx(42.25)


def test_readers_without_collectives_or_modules_give_nothing():
    flat = {"devices": {DEVS[0]: [["%fusion.1 = f32[8]{0} fusion()", 0,
                                   10]]},
            "host": [], "window": [0, 100]}
    bare = {"trace": flat, "program": {"spans": [], "modules": {}},
            "config": CFG}
    for name in ("collective_exposed.tp4", "collective_ms.tp4",
                 "decode_device_ms.tp4"):
        assert read(name, bare) is None


def test_one_chips_work_is_a_quarter_of_the_model():
    """Every projection and the untied head split by output columns, and
    attention by heads: one chip's FLOPs are a quarter of the model's."""
    tp = sum(2.0 * m * k * n for m, k, n in TP.forward_gemms(CFG, 32, 32))
    whole = sum(2.0 * m * k * n for m, k, n in K.forward_gemms(CFG, 32, 32))
    assert tp * 4 == whole
    c = {"decodes": [(10.0, 3, [40, 17, 100, 0])], "page_size": 16}
    work, calls = TP.decode_attention({"config": CFG, "counters": c})
    full = W.decode_attention([40, 17, 100], heads=16, kv_heads=4,
                              head_dim=16, page_size=16).scaled(2)
    assert calls == 2 and work.flops * 4 == full.flops
