"""Operations and bytes of the kernel calls at the configurations' shapes,
worked by hand."""

import json
import os

import pytest

import kernels as K
import work as W

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(name):
    with open(os.path.join(CHIP, "configs", name + ".json")) as f:
        return json.load(f)


def test_gemm_mlp_up_train_shape():
    # qwen2-0.5b mlp up-projection on 8 x 512 tokens, f32 operands
    w = W.gemm(4096, 896, 4864, a_bytes=4, b_bytes=4, out_bytes=4)
    assert w.flops == 2 * 4096 * 896 * 4864 == 35_701_915_648
    assert w.bytes == 4 * (4096 * 896 + 896 * 4864 + 4096 * 4864)


def test_bwd_pair_is_two_gemms():
    w = W.bwd_pair(4096, 896, 4864, dy_bytes=4, x_bytes=1, w_bytes=1,
                   dx_bytes=4, dw_bytes=4)
    assert w.flops == 2 * W.gemm(4096, 896, 4864, a_bytes=0, b_bytes=0,
                                 out_bytes=0).flops
    assert w.bytes == (4096 * 4864 * 4 + 4096 * 896 + 896 * 4864
                       + 4096 * 896 * 4 + 896 * 4864 * 4)


def test_decode_attention_qwen2_1_5b():
    # one row at context 100: 7 pages of 16 tokens, 12 q / 2 kv heads of 128
    w = W.decode_attention([100, 0], heads=12, kv_heads=2, head_dim=128,
                           page_size=16)
    assert w.flops == 4 * 12 * 128 * 100
    assert w.bytes == 2 * 7 * (2 * 16 * 128 + 4) + 12 * 128 * 8


def test_prefill_attention_causal_count():
    w = W.prefill_attention(256, 256, heads=12, kv_heads=2, head_dim=128,
                            page_size=16)
    assert w.flops == 4 * 12 * 128 * (256 * 256 + 256 * 257 / 2)
    assert w.bytes == 2 * 32 * (2 * 16 * 128 + 4) + 256 * 12 * 128 * 8


def test_param_counts_match_published_sizes():
    small, big = config("qwen2-0.5b.train"), config("qwen2-1.5b.serve")
    assert W.param_count(small)["total"] == 494_032_768
    assert W.param_count(big)["total"] == 1_543_714_304


def test_train_flops_per_token():
    cfg = config("qwen2-0.5b.train")
    n = W.param_count(cfg)["matmul"]
    assert n == 24 * (2 * 896 * 896 + 2 * 896 * 128 + 3 * 896 * 4864) \
        + 896 * 151936
    assert W.train_flops_per_token(cfg, 512) == 6 * n + 12 * 24 * 896 * 512


def test_forward_gemm_enumeration():
    cfg = config("qwen2-0.5b.train")
    calls = K.forward_gemms(cfg, 4096, 4096)
    assert len(calls) == 7 * 24 + 1
    assert calls[-1] == (4096, 896, 151936)
    flops = sum(2 * m * k * n for m, k, n in calls)
    assert flops == 2 * 4096 * W.param_count(cfg)["matmul"]


def test_roofline_bound():
    peak = W.peaks("TPU v5 lite")
    t, bound = W.roofline_s(W.Work(197e12, 1.0), peak)
    assert (t, bound) == (1.0, "compute")
    t, bound = W.roofline_s(W.Work(1.0, 819e9), peak)
    assert (t, bound) == (1.0, "bandwidth")


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        W.peaks("TPU v9 imaginary")
