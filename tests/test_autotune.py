"""Kernel registry + block-size autotuner: candidate enumeration invariants,
JSON tuning-table round-trip, and the trace-time consult used by qdot."""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

from repro.kernels import autotune


@pytest.fixture
def tmp_table(tmp_path):
    """Point the process-global tuning table at a scratch file."""
    t = autotune.set_table_path(str(tmp_path / "autotune.json"))
    yield t
    autotune.set_table_path(None)


def test_registry_contains_all_kernels():
    reg = autotune.registered_kernels()
    assert {"qmatmul", "qmatmul_fused", "quantize"} <= set(reg)
    from repro.kernels.fused import qmatmul_fused

    assert autotune.get_kernel("qmatmul_fused") is qmatmul_fused
    with pytest.raises(KeyError):
        autotune.get_kernel("nope")


def test_candidates_pin_block_k_to_chunk():
    # narrow accumulation: block_k is the rounding cadence n1 — numerics,
    # not schedule — so every candidate must carry it unchanged
    for bm, bn, bk in autotune.candidate_blocks(512, 4096, 512, chunk=64):
        assert bk == 64
    # wide accumulation: block_k still fixes the f32 partial-sum grouping,
    # so it is pinned at the 128 default rather than swept — tuning state
    # must never change results
    bks = {bk for _, _, bk in autotune.candidate_blocks(512, 4096, 512, chunk=0)}
    assert bks == {128}


def test_candidates_respect_vmem_budget():
    budget = 512 * 1024
    for bm, bn, bk in autotune.candidate_blocks(
            4096, 4096, 4096, chunk=0, vmem_budget=budget):
        assert autotune.vmem_block_bytes(bm, bn, bk) <= budget
    # never empty, even under an impossible budget
    assert autotune.candidate_blocks(4096, 4096, 4096, chunk=64, vmem_budget=1)


def test_candidates_do_not_exceed_padded_dims():
    cands = autotune.candidate_blocks(8, 64, 8, chunk=64)
    assert cands == [(128, 128, 64)]


def test_vmem_accounting_includes_residual_tiles():
    plain = autotune.vmem_block_bytes(128, 128, 128)
    emitq = autotune.vmem_block_bytes(128, 128, 128, emit_quantized=True)
    assert emitq == plain + 2 * 128 * 128 * 4


def test_autotune_roundtrip_and_trace_time_consult(tmp_table):
    # untuned shape falls back to the safe default
    assert autotune.blocks_for(64, 256, 64, 64) == (128, 128, 64)
    entry = autotune.autotune_qmatmul(64, 256, 64, chunk=0, reps=1)
    assert {"block_m", "block_n", "block_k", "us", "candidates"} <= set(entry)
    # consult returns the tuned winner...
    assert autotune.blocks_for(64, 256, 64, 0) == (
        entry["block_m"], entry["block_n"], entry["block_k"])
    # ...and the JSON file round-trips through a fresh table object
    assert os.path.exists(tmp_table.path)
    disk = json.load(open(tmp_table.path))
    assert disk == autotune.TuningTable(tmp_table.path).entries()
    # re-tuning the same shape is a cache hit (no re-timing)
    again = autotune.autotune_qmatmul(64, 256, 64, chunk=0, reps=1)
    assert again == entry


def test_tuned_blocks_do_not_change_qdot_numerics(tmp_table):
    # tuning only reshapes the schedule: qdot output is bit-identical
    # before and after the table is filled
    import jax.numpy as jnp

    from repro.core.policy import GEMMPrecision
    from repro.kernels.ops import QDotConfig, qdot
    from repro.quant.formats import FP8_152

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.standard_normal((130, 256)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((256, 200)).astype(np.float32))
    p = GEMMPrecision(m_acc=7, e_acc=6, chunk=64)
    cfg = QDotConfig(fwd=p, bwd=p, grad=p, repr_fmt=FP8_152)
    before = np.asarray(qdot(x, w, cfg))
    autotune.autotune_qmatmul(130, 256, 200, chunk=64, e_acc=6, m_acc=7,
                              repr_fmt=(5, 2), reps=1)
    assert autotune.get_table().get(
        130, 256, 200, 64, e_acc=6, m_acc=7, repr_fmt=(5, 2)) is not None
    after = np.asarray(qdot(x, w, cfg))
    np.testing.assert_array_equal(before, after)


def test_narrow_chunk0_numerics_immune_to_tuning(tmp_table):
    # GEMMPrecision(chunk=0) is a legal *narrow* config ("sequential,
    # oracle only"): the tuner must not reinterpret chunk 0 as "block_k is
    # free" — the fused path has to keep matching the unfused oracle
    # bit-for-bit after its shape is tuned
    import jax.numpy as jnp

    from repro.core.policy import GEMMPrecision
    from repro.kernels.ops import QDotConfig, qdot
    from repro.quant.formats import FP8_152

    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.standard_normal((64, 512)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((512, 64)).astype(np.float32))
    p = GEMMPrecision(m_acc=5, e_acc=6, chunk=0)
    fused = QDotConfig(fwd=p, bwd=p, grad=p, repr_fmt=FP8_152)
    oracle = QDotConfig(fwd=p, bwd=p, grad=p, repr_fmt=FP8_152, fused=False)
    autotune.autotune_qmatmul(64, 512, 64, chunk=0, e_acc=6, m_acc=5,
                              repr_fmt=(5, 2), reps=1)
    np.testing.assert_array_equal(
        np.asarray(qdot(x, w, fused)), np.asarray(qdot(x, w, oracle)))


def test_warmup_gemm_autotune_fills_table(tmp_table):
    import jax

    from repro.configs import get_smoke_config
    from repro.core.policy import AccumulationPolicy, plan_for_model
    from repro.kernels.ops import qdot_gemm_variants
    from repro.models.api import dense_gemm_shapes, get_model
    from repro.train.loop import warmup_gemm_autotune

    cfg = get_smoke_config("qwen2-1.5b")
    cfg = plan_for_model(cfg, seq_len=8, global_batch=1,
                         policy=AccumulationPolicy(mode="predicted"))
    shapes = dense_gemm_shapes(cfg, seq_len=8, global_batch=1)
    assert shapes, "smoke config must expose quantized dense GEMMs"
    model = get_model(cfg)
    results = warmup_gemm_autotune(model, seq_len=8, global_batch=1, reps=1)
    # every (layer, role) kernel variant got a table entry — FWD in train
    # (packed residual emission) and eval flavors, plus the one-pass
    # backward pair (or its two-GEMM fallback); the role list comes from
    # qdot_gemm_variants, the same source ops.py traces from
    want = sum(len(qdot_gemm_variants(qcfg, t, k, n))
               for _, t, k, n, qcfg in shapes)
    assert len(results) == want
    for tag, t, k, n, qcfg in shapes:
        p = qcfg.fwd
        chunk = p.chunk if p is not None and p.chunk > 0 else 0
        e_acc, m_acc = (8, 23) if p is None else (p.e_acc, p.m_acc)
        fmt = (None if qcfg.repr_fmt is None
               else (qcfg.repr_fmt.e, qcfg.repr_fmt.m))
        # the FWD role is tuned with packed residual emission on — the
        # exact kernel variant the training step traces
        assert autotune.get_table().get(
            t, k, n, chunk, e_acc=e_acc, m_acc=m_acc, repr_fmt=fmt,
            emit_quantized=fmt is not None,
            pack_residuals=qcfg.packs) is not None
        roles = qdot_gemm_variants(qcfg, t, k, n)
        if "bwd_pair" in roles:
            kw = dict(roles["bwd_pair"])
            kw.pop("kernel")
            bt, bk, bn = autotune.pair_blocks_for(
                kw.pop("t"), kw.pop("k"), kw.pop("n"), **kw)
            assert f"{tag}:bwd_pair" in results
            assert bk == results[f"{tag}:bwd_pair"]["block_k"]


def test_table_key_carries_dtype_and_vmem_ceiling(tmp_table):
    # the same shape tuned under a different operand dtype or VMEM ceiling
    # must not share an entry — a v6e-tuned table cannot leak v6e-sized
    # working sets onto a v4 core, nor i8-operand blocks onto f32 GEMMs
    e = autotune.autotune_qmatmul(64, 256, 64, chunk=64, e_acc=6, m_acc=5,
                                  repr_fmt=(5, 2), reps=1)
    assert autotune.blocks_for(
        64, 256, 64, 64, e_acc=6, m_acc=5, repr_fmt=(5, 2)
    ) == (e["block_m"], e["block_n"], 64)
    # same shape, packed-B operand: distinct key -> untuned default
    assert autotune.blocks_for(
        64, 256, 64, 64, e_acc=6, m_acc=5, repr_fmt=(5, 2),
        quantize_b=False, dtype=autotune.operand_dtype(False, True)
    ) == (128, 128, 64)
    # same shape, other-generation ceiling: distinct key -> untuned default
    assert autotune.blocks_for(
        64, 256, 64, 64, e_acc=6, m_acc=5, repr_fmt=(5, 2),
        vmem=autotune.VMEM_PER_GENERATION["v6e"] // 2
    ) == (128, 128, 64)


def test_vmem_budget_per_generation(monkeypatch):
    monkeypatch.delenv("REPRO_VMEM_BUDGET", raising=False)

    class FakeTPU:
        platform = "tpu"

        def __init__(self, kind):
            self.device_kind = kind

    # the generation comes from the device kind JAX reports
    assert autotune.device_generation(FakeTPU("TPU v5 lite")) == "v5e"
    assert autotune.device_generation(FakeTPU("TPU v6 lite")) == "v6e"
    assert autotune.device_generation(FakeTPU("TPU v4")) == "v4"
    # an unknown TPU is an error, never a guessed ceiling
    with pytest.raises(ValueError, match="unknown TPU device_kind"):
        autotune.device_generation(FakeTPU("TPU v99"))
    # off the TPU the budget plans for the chip the repo is measured on
    assert autotune.vmem_budget() == autotune.VMEM_PER_GENERATION[
        autotune.HOST_GENERATION] // 2
    monkeypatch.setattr(autotune, "device_generation",
                        lambda device=None: "v6e")
    assert autotune.vmem_budget() == autotune.VMEM_PER_GENERATION["v6e"] // 2
    assert autotune.vmem_budget("v4") == autotune.VMEM_PER_GENERATION["v4"] // 2
    monkeypatch.setenv("REPRO_VMEM_BUDGET", "12345")
    assert autotune.vmem_budget() == 12345


def test_vmem_accounting_prices_packed_carriers():
    plain = autotune.vmem_block_bytes(128, 128, 128)
    packed_ops = autotune.vmem_block_bytes(128, 128, 128, operand_bytes=1)
    assert plain - packed_ops == 3 * (2 * 128 * 128)
    emit_f32 = autotune.vmem_block_bytes(128, 128, 128, emit_quantized=True)
    emit_i8 = autotune.vmem_block_bytes(128, 128, 128, emit_quantized=True,
                                        residual_bytes=1)
    assert emit_f32 - emit_i8 == 3 * (2 * 128 * 128)


def test_autotune_bwd_pair_roundtrip(tmp_table):
    # pair tuning sweeps only block_k (block_t/block_n are the two rounding
    # cadences) and the consult returns the tuned winner
    entry = autotune.autotune_bwd_pair(
        64, 256, 64, bwd_chunk=64, grad_chunk=64, bwd_acc=(6, 5),
        grad_acc=(6, 8), repr_fmt=(5, 2), packed=True, reps=1)
    assert entry["block_t"] == 64 and entry["block_n"] == 64
    bt, bk, bn = autotune.pair_blocks_for(
        64, 256, 64, bwd_chunk=64, grad_chunk=64, bwd_acc=(6, 5),
        grad_acc=(6, 8), repr_fmt=(5, 2), packed=True)
    assert (bt, bk, bn) == (64, entry["block_k"], 64)
    # cache hit on re-tune
    again = autotune.autotune_bwd_pair(
        64, 256, 64, bwd_chunk=64, grad_chunk=64, bwd_acc=(6, 5),
        grad_acc=(6, 8), repr_fmt=(5, 2), packed=True, reps=1)
    assert again == entry


def test_attn_call_needs_a_page_row():
    # an AttnCall describes the paged prefill kernel only: a bucket's
    # padded page-row width is required and positive
    with pytest.raises(ValueError, match="max_pages"):
        autotune.AttnCall(8, 4, 16, 4, 0)
    call = autotune.AttnCall(8, 4, 16, 4, 3, e_acc=6, m_acc=7,
                             kv_fmt=(5, 2))
    assert call.table_key().startswith("attn:8x4x16:c4:acc6.7:r5.2:")
    assert call.table_key().endswith(":p3")


def test_autotune_flash_prefill_roundtrip(tmp_table):
    # the prefill tuner times the paged kernel and records under the
    # call's :p{max_pages} key, which the trace-time consult then reads
    call = autotune.AttnCall(8, 4, 16, 4, 3, e_acc=6, m_acc=7,
                             kv_fmt=(5, 2), kv_heads=2)
    entry = autotune.autotune_flash_prefill(call, reps=1)
    assert tmp_table.get_key(call.table_key()) == entry
    assert autotune.attn_blocks_for(
        8, 4, 16, 4, max_pages=3, e_acc=6, m_acc=7, kv_fmt=(5, 2)
    ) == entry["block_q"]
    # another bucket width is another key: untuned default
    assert autotune.attn_blocks_for(
        8, 4, 16, 4, max_pages=4, e_acc=6, m_acc=7, kv_fmt=(5, 2)) == 128
