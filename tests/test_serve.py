"""Serving subsystem tests: paged QTensor KV-cache, flash attention
kernels vs their unfused oracles (bit-exact), the inference-side
accumulator planner, the serve-time VRR monitor, and the
continuous-batching scheduler (page accounting + cross-sequence
isolation; hypothesis property tests over arrival/completion orders)."""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.vrr import CUTOFF_LOG_V
from repro.kernels.common import N_STATS, quantize_block
from repro.kernels.attention import (
    flash_prefill_paged,
    flash_prefill_paged_reference,
    paged_attn_decode,
    paged_attn_decode_reference,
)
from repro.models import encdec, lm
from repro.models.api import DecodeRequest, get_model
from repro.quant.formats import FP8_152, FPFormat
from repro.serve import kvcache as KV
from repro.serve.plan import decode_m_acc, min_e_acc, plan_attention
from repro.serve.scheduler import ServeEngine, measure_decode_vrr
from tests.conftest import layer_sliced_arena

ACC = (6, 7)


def _filled_arena(rng, *, kv=2, dh=16, n_pages=10, page_size=4,
                  seq_tokens=(7, 3), fmt=FP8_152, scale=1.0, width=None):
    """One-layer arena with each sequence's K/V written via write_prompt;
    returns (arena dict of layer-0 slices, page table rows, lens).  The
    table is as wide as the longest row, or ``width`` pages."""
    pc = KV.PagedKVConfig(n_layers=1, n_kv_heads=kv, head_dim=dh,
                          n_pages=n_pages, page_size=page_size, kv_fmt=fmt)
    ar = KV.init_arena(pc)
    ka, kse = ar["k"][0], ar["k_se"][0]
    va, vse = ar["v"][0], ar["v_se"][0]
    rows, next_page, ks, vs = [], 1, [], []  # page 0 reserved
    for n in seq_tokens:
        npg = -(-n // page_size)
        rows.append(list(range(next_page, next_page + npg)))
        next_page += npg
        # each row padded to whole pages with zeros, as write_prompt pads
        # its tail page: every row goes into the arena in one write
        pad = ((0, npg * page_size - n), (0, 0), (0, 0))
        ks.append(np.pad(rng.standard_normal((n, kv, dh)), pad) * scale)
        vs.append(np.pad(rng.standard_normal((n, kv, dh)), pad) * scale)
    pages = jnp.asarray([p for r in rows for p in r], jnp.int32)
    if len(pages):
        k, v = (jnp.asarray(np.concatenate(x).astype(np.float32))
                for x in (ks, vs))
        ka, kse = KV.write_prompt(ka, kse, k, pages, fmt)
        va, vse = KV.write_prompt(va, vse, v, pages, fmt)
    width = width or max(len(r) for r in rows)
    pt = np.zeros((len(rows), width), np.int32)
    for i, r in enumerate(rows):
        pt[i, :len(r)] = r
    return ({"k": ka, "v": va, "k_se": kse, "v_se": vse},
            jnp.asarray(pt), jnp.asarray(list(seq_tokens), jnp.int32))


# --------------------------------------------------------------------------
# decode kernel bit-exactness
# --------------------------------------------------------------------------


# 16-token pages in a 32-page table: the kernel walks two 16-page blocks a
# row, so rows end mid-block, on block and page edges, or skip a block
_BLOCKS = dict(page_size=16, width=32)


@pytest.mark.parametrize("acc,seq_tokens,geom", [
    *(pytest.param(acc, seq, {}, id=f"acc{i}-seq_tokens{j}")
      for i, acc in enumerate([(8, 23), (6, 23), ACC, (6, 5)])
      for j, seq in enumerate([
          (7, 3),        # ragged page tails
          (8, 4),        # decode exactly at page boundaries
          (9, 1, 12),    # boundary + single-token + multi-page
      ])),
    pytest.param(ACC, (300, 40, 500), _BLOCKS, id="blocks-mid_block"),
    pytest.param(ACC, (256, 512, 16), _BLOCKS, id="blocks-block_edge"),
    pytest.param(ACC, (1, 400), _BLOCKS, id="blocks-one_token"),
    pytest.param(ACC, (0, 333, 0), _BLOCKS, id="blocks-inactive_row"),
    pytest.param(ACC, (100, 37), dict(page_size=16, width=8),
                 id="narrower_than_a_block"),
    pytest.param((6, 5), (512, 77, 257), dict(_BLOCKS, h=12),
                 id="blocks-kv2_g6"),
    pytest.param(ACC, (290, 0, 64), dict(_BLOCKS, return_carry=True),
                 id="blocks-carry"),
    # dh 128: the arena is copied by the kernel's own DMAs
    pytest.param((6, 5), (450, 3), dict(_BLOCKS, dh=128, return_carry=True),
                 id="blocks-dh128-carry"),
])
def test_paged_decode_bitexact_vs_oracle(acc, seq_tokens, geom):
    geom = dict(geom)
    h, carry = geom.pop("h", 4), geom.pop("return_carry", False)
    dh = geom.get("dh", 16)
    page_size = geom.get("page_size", 4)
    n_pages = max(16, 1 + sum(-(-n // page_size) for n in seq_tokens))
    rng = np.random.RandomState(0)
    arena, pt, lens = _filled_arena(rng, seq_tokens=seq_tokens,
                                    n_pages=n_pages, **geom)
    q = jnp.asarray(rng.standard_normal((len(seq_tokens), h, dh))
                    .astype(np.float32))
    args = (q, arena["k"], arena["v"], arena["k_se"], arena["v_se"], pt, lens)
    out = paged_attn_decode(*args, kv_fmt=FP8_152, acc=acc,
                            return_carry=carry)
    ref = paged_attn_decode_reference(*args, kv_fmt=FP8_152, acc=acc,
                                      return_carry=carry)
    for got, want in zip(out if carry else [out], ref if carry else [ref]):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert np.all(np.isfinite(np.asarray(got)))


def test_paged_decode_packed_vs_f32_parity():
    """The kernel fed int8 pages must equal the kernel fed the dequantized
    f32 carrier of the same pages — the in-VMEM unpack is value-neutral."""
    rng = np.random.RandomState(1)
    # a large scale exercises the per-page scale-exponent path
    arena, pt, lens = _filled_arena(rng, seq_tokens=(7, 3), scale=37.0)
    q = jnp.asarray(rng.standard_normal((2, 4, 16)).astype(np.float32))
    packed = paged_attn_decode(q, arena["k"], arena["v"], arena["k_se"],
                               arena["v_se"], pt, lens, kv_fmt=FP8_152, acc=ACC)
    kf = KV.dequantize_pages(arena["k"], arena["k_se"], FP8_152)
    vf = KV.dequantize_pages(arena["v"], arena["v_se"], FP8_152)
    zero = jnp.zeros_like(arena["k_se"])
    f32 = paged_attn_decode(q, kf, vf, zero, zero, pt, lens,
                            kv_fmt=None, acc=ACC)
    np.testing.assert_array_equal(np.asarray(packed), np.asarray(f32))


def test_paged_decode_inactive_row_and_stats_neutrality():
    rng = np.random.RandomState(2)
    arena, pt, lens = _filled_arena(rng, seq_tokens=(7, 3))
    q = jnp.asarray(rng.standard_normal((2, 4, 16)).astype(np.float32))
    lens0 = lens.at[1].set(0)  # padded/inactive row
    out = paged_attn_decode(q, arena["k"], arena["v"], arena["k_se"],
                            arena["v_se"], pt, lens0, kv_fmt=FP8_152, acc=ACC)
    assert np.all(np.asarray(out[1]) == 0.0)
    # the telemetry epilogue must not change the attention output
    with_stats, raw = paged_attn_decode(
        q, arena["k"], arena["v"], arena["k_se"], arena["v_se"], pt, lens,
        kv_fmt=FP8_152, acc=ACC, collect_stats=True)
    plain = paged_attn_decode(q, arena["k"], arena["v"], arena["k_se"],
                              arena["v_se"], pt, lens, kv_fmt=FP8_152, acc=ACC)
    np.testing.assert_array_equal(np.asarray(with_stats), np.asarray(plain))
    assert raw.shape == (N_STATS,) and float(raw[0]) > 0


# --------------------------------------------------------------------------
# prefill kernel
# --------------------------------------------------------------------------

# the prefill tests' page size (== the carry cadence) and heads: 4 query
# heads over 2 KV heads, so GQA is always on
_PS, _H, _KV, _DH = 4, 4, 2, 16


def _prefill_qkv(seed, s):
    rng = np.random.RandomState(seed)
    q, k, v = (jnp.asarray(rng.standard_normal((s, n, _DH))
                           .astype(np.float32)) for n in (_H, _KV, _KV))
    return q, k, v


def _prefill_arena(k, v, row, packed, n_pages=12):
    """One-layer arena holding ``k``/``v`` (S, KV, dh) on the pages of
    ``row`` in token order: int8 (1,5,2) pages written by ``write_prompt``,
    or f32 carrier pages (scale exponents 0).  Returns the kernel's arena
    operands and its ``kv_fmt``."""
    pages = jnp.asarray(row, jnp.int32)
    se = jnp.zeros((n_pages,), jnp.int32)
    if packed:
        zero = jnp.zeros((n_pages, _KV, _PS, _DH), jnp.int8)
        kp, kse = KV.write_prompt(zero, se, k, pages, FP8_152)
        vp, vse = KV.write_prompt(zero, se, v, pages, FP8_152)
        return (kp, vp, kse, vse), FP8_152
    s = k.shape[0]

    def blocks(x):
        x = jnp.pad(x, ((0, len(row) * _PS - s), (0, 0), (0, 0)))
        return x.reshape(len(row), _PS, _KV, _DH).transpose(0, 2, 1, 3)

    zero = jnp.zeros((n_pages, _KV, _PS, _DH), jnp.float32)
    return (zero.at[pages].set(blocks(k)), zero.at[pages].set(blocks(v)),
            se, se), None


def _prefill_row(s, width=None):
    """Non-contiguous pages in token order, padded with the null page."""
    n = -(-s // _PS)
    row = [11 - 2 * i for i in range(n)]
    return row, jnp.asarray(row + [0] * ((width or n + 1) - n), jnp.int32)


@pytest.mark.parametrize("packed", [False, True], ids=["f32", "int8"])
@pytest.mark.parametrize("s", [5, 8, 13])
@pytest.mark.parametrize("acc", [(8, 23), ACC])
def test_flash_prefill_bitexact_and_blockq_invariant(s, acc, packed):
    """The served prefill kernel equals its jnp oracle bit for bit, at
    every ``block_q``, over f32 and int8-packed pages."""
    q, k, v = _prefill_qkv(3, s)
    pages, row = _prefill_row(s)
    arena, fmt = _prefill_arena(k, v, pages, packed)
    args = (q, *arena, row, 0, s, s)
    ref = flash_prefill_paged_reference(*args, kv_fmt=fmt, acc=acc)
    for bq in (4, 8):
        out = flash_prefill_paged(*args, kv_fmt=fmt, acc=acc, block_q=bq)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_flash_prefill_matches_plain_softmax_when_wide():
    s = 11
    q, k, v = _prefill_qkv(4, s)
    pages, row = _prefill_row(s)
    arena, _ = _prefill_arena(k, v, pages, packed=False)
    out = flash_prefill_paged(q, *arena, row, 0, s, s, acc=(8, 23),
                              block_q=8)
    kh = jnp.repeat(k, _H // _KV, axis=1)
    vh = jnp.repeat(v, _H // _KV, axis=1)
    sc = jnp.einsum("shd,thd->hst", q, kh) / np.sqrt(_DH)
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None], sc, -jnp.inf)
    ref = jnp.einsum("hst,thd->shd", jax.nn.softmax(sc, axis=-1), vh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------
# chunked prefill: resumable carry bit-exactness
# --------------------------------------------------------------------------


@pytest.mark.parametrize("s", [13, 16])  # ragged page tail / exact boundary
@pytest.mark.parametrize("acc", [(8, 23), ACC])
def test_flash_prefill_resumable_carry_bitexact(s, acc):
    """Splitting the KV walk at ANY page boundary and resuming with the
    carried (o, m, l) must be bit-identical to the one-shot kernel and the
    unfused oracle — the carry is exact through HBM because o/l are
    representable accumulator-format points and the running max is on the
    integer lattice."""
    q, k, v = _prefill_qkv(11, s)
    pages, row = _prefill_row(s)
    arena, fmt = _prefill_arena(k, v, pages, packed=True)
    kw = dict(kv_fmt=fmt, acc=acc)
    one = flash_prefill_paged(q, *arena, row, 0, s, s, block_q=8, **kw)
    ref = flash_prefill_paged_reference(q, *arena, row, 0, s, s, **kw)
    np.testing.assert_array_equal(np.asarray(one), np.asarray(ref))
    for split in range(1, len(pages)):
        for walk, bq in ((flash_prefill_paged, dict(block_q=8)),
                         (flash_prefill_paged_reference, {})):
            c = walk(q, *arena, row, 0, s, split * _PS, return_carry=True,
                     **bq, **kw)
            out = walk(q, *arena, row, 0, s, s, carry=c, start_page=split,
                       **bq, **kw)
            np.testing.assert_array_equal(np.asarray(out), np.asarray(one),
                                          err_msg=f"{walk.__name__} {split}")


@pytest.mark.parametrize("s,c_slab", [(13, 4), (13, 8), (16, 8), (9, 12)])
def test_flash_prefill_qslab_scheme_bitexact(s, c_slab):
    """The engine's chunked-prefill discipline — per query slab, write the
    slab's K/V into its int8 pages, then one kernel call over the
    post-write arena with traced ``q_offset``/``q_len`` and the slab padded
    to a fixed width — concatenates to exactly the one-shot output for
    every slab size, including ragged final slabs, and leaves the same
    arena bytes."""
    q, k, v = _prefill_qkv(12, s)
    pages, row = _prefill_row(s)
    arena, fmt = _prefill_arena(k, v, pages, packed=True)
    one = flash_prefill_paged(q, *arena, row, 0, s, s, kv_fmt=fmt, acc=ACC,
                              block_q=8)
    kp = vp = jnp.zeros_like(arena[0])
    kse = vse = jnp.zeros_like(arena[2])
    outs, t0 = [], 0
    while t0 < s:
        t1 = min(t0 + c_slab, s)
        slab = jnp.asarray(pages[t0 // _PS:-(-t1 // _PS)], jnp.int32)
        kp, kse = KV.write_prompt(kp, kse, k[t0:t1], slab, fmt)
        vp, vse = KV.write_prompt(vp, vse, v[t0:t1], slab, fmt)
        qs = jnp.zeros((c_slab, _H, _DH), jnp.float32).at[:t1 - t0].set(
            q[t0:t1])
        o = flash_prefill_paged(qs, kp, vp, kse, vse, row, jnp.int32(t0),
                                jnp.int32(t1 - t0), jnp.int32(t1),
                                kv_fmt=fmt, acc=ACC, block_q=8)
        outs.append(np.asarray(o[:t1 - t0]))
        t0 = t1
    np.testing.assert_array_equal(np.concatenate(outs, 0), np.asarray(one))
    for got, want in zip((kp, vp, kse, vse), arena):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_flash_prefill_never_reads_past_the_live_pages():
    """Page-row entries at or past ``ceil(kv_len / page_size)`` are never
    read: pointed at a page of NaNs, the kernel still equals the oracle
    over the clean row (the oracle walks every entry, so it runs on the
    clean row only).  Two geometries put such pages in the causal view: a
    slab padded past its live rows (raw carry out, so padded rows show),
    and a history pass whose queries sit past ``kv_len``."""
    s = 13
    q, k, v = _prefill_qkv(13, s)
    pages, clean = _prefill_row(s, width=6)
    arena, _ = _prefill_arena(k, v, pages, packed=False)
    nan_page = 1
    assert nan_page not in pages
    kp, vp, kse, vse = (arena[0].at[nan_page].set(jnp.nan),
                        arena[1].at[nan_page].set(jnp.nan), *arena[2:])
    for q_off, q_len, kv_len, width, carry in ((0, s, s, 24, True),
                                               (8, 5, 8, 8, False)):
        qs = jnp.zeros((width, _H, _DH), jnp.float32).at[:q_len].set(
            q[q_off:q_off + q_len])
        dirty = clean.at[-(-kv_len // _PS):].set(nan_page)
        geom = (q_off, q_len, kv_len)
        ref = flash_prefill_paged_reference(qs, *arena, clean, *geom,
                                            acc=ACC, return_carry=carry)
        out = flash_prefill_paged(qs, kp, vp, kse, vse, dirty, *geom,
                                  acc=ACC, block_q=8, return_carry=carry)
        for got, want in zip(out if carry else [out], ref if carry else [ref]):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                          err_msg=str(geom))
            assert np.all(np.isfinite(np.asarray(got)))


@pytest.mark.parametrize("n,c_slab", [(13, 8), (16, 8), (9, 12)])
def test_prefill_chunk_paged_bitexact_vs_oneshot(n, c_slab):
    """Whole-model chunked prefill == one-shot ``prefill_paged``: same
    final logits AND byte-identical arena (codes + scale exponents) for
    ragged tails, page-boundary prompts and slabs larger than the
    prompt."""
    cfg = get_smoke_config("qwen2-1.5b")
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.RandomState(14)
    page = 4
    toks = jnp.asarray([rng.randint(0, cfg.vocab_size, n)], jnp.int32)
    pages = list(range(1, -(-n // page) + 1))
    kv1 = lm.init_paged_state(cfg, n_pages=12, page_size=page)
    pg_ids = jnp.asarray(pages, jnp.int32)
    l1, kv1 = lm.paged_prefill(params, toks, kv1, pg_ids, pg_ids, 0, n, cfg,
                               kv_fmt=FP8_152, acc=ACC)
    kv2 = lm.init_paged_state(cfg, n_pages=12, page_size=page)
    t0 = 0
    while t0 < n:
        t1 = min(t0 + c_slab, n)
        hist = pages[:t0 // page]
        slab = pages[t0 // page:-(-t1 // page)]
        l2, kv2 = lm.paged_prefill(
            params, toks[:, t0:t1], kv2,
            jnp.asarray(hist + slab, jnp.int32),
            jnp.asarray(slab, jnp.int32), t0, t1 - t0, cfg, kv_fmt=FP8_152,
            acc=ACC, want_logits=(t1 == n))
        t0 = t1
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
    for key in kv1:
        np.testing.assert_array_equal(np.asarray(kv1[key]),
                                      np.asarray(kv2[key]))


# --------------------------------------------------------------------------
# kv-cache packing
# --------------------------------------------------------------------------


def test_write_prompt_then_append_token_roundtrip():
    """Decode appends into the tail page a prefill started must dequantize
    under the page's original scale; page-0 writes never leak."""
    rng = np.random.RandomState(5)
    fmt = FP8_152
    pc = KV.PagedKVConfig(n_layers=1, n_kv_heads=2, head_dim=8,
                          n_pages=6, page_size=4, kv_fmt=fmt)
    ar = KV.init_arena(pc)
    ka, kse = ar["k"][0], ar["k_se"][0]
    x = jnp.asarray(rng.standard_normal((6, 2, 8)).astype(np.float32))
    ka, kse = KV.write_prompt(ka, kse, x, jnp.asarray([1, 2]), fmt)
    deq = KV.dequantize_pages(ka, kse, fmt)
    # token 6 lands in page 2 slot 2 under page 2's EXISTING scale, leaving
    # the earlier tokens' codes and decoded values untouched
    tok = jnp.asarray(rng.standard_normal((1, 2, 8)).astype(np.float32))
    ka2, kse2 = KV.append_token(ka, kse, tok, jnp.asarray([2]),
                                jnp.asarray([2]), fmt)
    assert int(kse2[2]) == int(kse[2])
    np.testing.assert_array_equal(np.asarray(ka2[1]), np.asarray(ka[1]))
    np.testing.assert_array_equal(np.asarray(ka2[2, :, :2]),
                                  np.asarray(ka[2, :, :2]))
    deq2 = KV.dequantize_pages(ka2, kse2, fmt)
    np.testing.assert_array_equal(np.asarray(deq2[1]), np.asarray(deq[1]))
    np.testing.assert_array_equal(np.asarray(deq2[2, :, :2]),
                                  np.asarray(deq[2, :, :2]))
    # a padded-row write (page_id 0) only ever touches the null page
    ka3, _ = KV.append_token(ka2, kse2, tok, jnp.asarray([0]),
                             jnp.asarray([0]), fmt)
    np.testing.assert_array_equal(np.asarray(ka3[1:]), np.asarray(ka2[1:]))


def test_write_prompt_pages_hold_the_rounded_prompt():
    """A prompt written over two non-contiguous pages with a ragged tail
    reads back (``dequantize_pages``) as its (1,5,2) rounding under each
    page's power-of-two scale, and the tail slots past the prompt hold
    code 0."""
    rng = np.random.RandomState(15)
    fmt = FP8_152
    pc = KV.PagedKVConfig(n_layers=1, n_kv_heads=2, head_dim=8,
                          n_pages=6, page_size=4, kv_fmt=fmt)
    ar = KV.init_arena(pc)
    x = jnp.asarray(rng.standard_normal((7, 2, 8)).astype(np.float32) * 5.0)
    pages = [3, 1]
    ka, kse = KV.write_prompt(ar["k"][0], ar["k_se"][0], x,
                              jnp.asarray(pages), fmt)
    deq = np.asarray(KV.dequantize_pages(ka, kse, fmt))
    xs = np.asarray(x)
    for i, pg in enumerate(pages):
        blk = xs[4 * i:4 * i + 4]                       # (<=4, KV, dh)
        se = int(np.floor(np.log2(np.abs(blk).max())))
        assert int(kse[pg]) == se
        want = np.asarray(quantize_block(jnp.asarray(blk) * 2.0 ** -se,
                                         fmt.e, fmt.m)) * 2.0 ** se
        got = deq[pg].transpose(1, 0, 2)[:len(blk)]     # token-major
        np.testing.assert_array_equal(got, want)
    # the ragged tail: token 7 of page 1 was never written
    assert np.all(np.asarray(ka[1, :, 3:]) == 0)


def test_swap_roundtrip_byte_identical():
    """swap-out -> swap-in must round-trip the packed pages BYTE-identically
    (int8 codes and int32 scale exponents), both onto the same pages and
    onto different pages (only the page table changes)."""
    rng = np.random.RandomState(16)
    fmt = FP8_152
    pc = KV.PagedKVConfig(n_layers=2, n_kv_heads=2, head_dim=8,
                          n_pages=10, page_size=4, kv_fmt=fmt)
    kv = KV.init_arena(pc)
    for layer in range(2):
        x = jnp.asarray(rng.standard_normal((7, 2, 8)).astype(np.float32)) * 9.0
        k, kse = KV.write_prompt(kv["k"][layer], kv["k_se"][layer], x,
                                 jnp.asarray([1, 2]), fmt)
        v, vse = KV.write_prompt(kv["v"][layer], kv["v_se"][layer], 2 * x,
                                 jnp.asarray([1, 2]), fmt)
        kv = {"k": kv["k"].at[layer].set(k), "v": kv["v"].at[layer].set(v),
              "k_se": kv["k_se"].at[layer].set(kse),
              "v_se": kv["v_se"].at[layer].set(vse)}
    blob = KV.swap_out_pages(kv, [1, 2])
    assert blob["k"].dtype == np.int8 and blob["k_se"].dtype == np.int32
    # scrub the pages, restore onto the SAME ids -> arena bytes identical
    scrubbed = {
        "k": kv["k"].at[:, [1, 2]].set(0), "v": kv["v"].at[:, [1, 2]].set(0),
        "k_se": kv["k_se"].at[:, [1, 2]].set(0),
        "v_se": kv["v_se"].at[:, [1, 2]].set(0)}
    back = KV.swap_in_pages(scrubbed, [1, 2], blob)
    for key in kv:
        np.testing.assert_array_equal(np.asarray(back[key]),
                                      np.asarray(kv[key]))
    # restore onto DIFFERENT ids -> the moved pages hold the same bytes
    moved = KV.swap_in_pages(scrubbed, [5, 7], blob)
    for a, b in ((5, 1), (7, 2)):
        np.testing.assert_array_equal(np.asarray(moved["k"][:, a]),
                                      np.asarray(kv["k"][:, b]))
        np.testing.assert_array_equal(np.asarray(moved["k_se"][:, a]),
                                      np.asarray(kv["k_se"][:, b]))
    # wrong blob size is rejected, not silently truncated
    with pytest.raises(ValueError, match="pages"):
        KV.swap_in_pages(scrubbed, [5], blob)


def test_swapstore_accounting():
    store = KV.SwapStore()
    blob = {"k": np.zeros((2, 1, 2, 4, 8), np.int8),
            "k_se": np.zeros((2, 1), np.int32)}
    store.put(7, blob, 3)
    assert 7 in store and len(store) == 1 and store.n_tokens(7) == 3
    assert store.bytes_used == blob["k"].nbytes + blob["k_se"].nbytes
    with pytest.raises(ValueError):
        store.put(7, blob, 3)
    got, n = store.take(7)
    assert got is blob and n == 3 and len(store) == 0


# --------------------------------------------------------------------------
# planner
# --------------------------------------------------------------------------


def test_planner_widths_monotone_and_knee_certified():
    page = 16
    plan = plan_attention(8192, page)
    ms = [b.m_acc for b in plan.buckets]
    assert ms == sorted(ms), "widths must be monotone in context length"
    from repro.telemetry.stats import predicted_kernel_vrr

    for b in plan.buckets:
        n2 = -(-b.max_ctx // page)
        v = n2 * (1.0 - predicted_kernel_vrr(b.m_acc, plan.m_p, page, n2))
        assert v < CUTOFF_LOG_V, f"bucket {b} fails its own knee test"
        if b.m_acc > plan.m_p and n2 > 1:
            v1 = n2 * (1.0 - predicted_kernel_vrr(b.m_acc - 1, plan.m_p,
                                                  page, n2))
            assert v1 >= CUTOFF_LOG_V, f"bucket {b} is not minimal"
    assert min_e_acc(1 << 20) >= 6
    assert decode_m_acc(page, page, 5) == 5  # single block: no carry rounding


def test_planner_bump_rebuckets_monotonically():
    plan = plan_attention(4096, 16)
    bumped = plan.bumped(0)
    assert bumped.buckets[0].m_acc == plan.buckets[0].m_acc + 1
    ms = [b.m_acc for b in bumped.buckets]
    assert ms == sorted(ms)


def test_planner_chunked_prefill_certification():
    """The carry-resumption re-run of the knee test: page-ALIGNED slab
    boundaries add zero carry-rounding events (the hand-off is an exact
    HBM round-trip — pinned bit-exactly by the kernel tests), so the plan
    records resumptions but assigns the same widths; an UNALIGNED slab
    size adds one event per resumption and can only widen."""
    from repro.serve.plan import extra_carry_events, max_carry_resumptions

    page = 16
    base = plan_attention(8192, page)
    aligned = plan_attention(8192, page, prefill_chunk_tokens=4 * page)
    assert aligned.prefill_chunk == 4 * page
    for b0, b1 in zip(base.buckets, aligned.buckets):
        assert b1.resumptions == max_carry_resumptions(b1.max_ctx, 4 * page)
        assert (b1.m_acc, b1.e_acc) == (b0.m_acc, b0.e_acc), (
            "aligned resumptions must not change the certified widths")
    assert aligned.buckets[-1].resumptions > 0
    # unaligned slabs: one extra quantized-carry event per resumption
    r = max_carry_resumptions(8192, 24)
    assert extra_carry_events(page, 24, r) == r
    assert extra_carry_events(page, 4 * page, r) == 0
    for ctx in (512, 2048, 8192):
        rr = max_carry_resumptions(ctx, 24)
        assert decode_m_acc(ctx, page, 5, extra_events=rr) >= \
            decode_m_acc(ctx, page, 5)
    # e_acc checks every materialization boundary, not just finalization
    assert min_e_acc(4096, boundaries=(1024, 2048, 3072)) == min_e_acc(4096)
    assert min_e_acc(64, boundaries=(4096,)) == min_e_acc(4096)


# --------------------------------------------------------------------------
# model decode paths through the cache + kernel
# --------------------------------------------------------------------------


def test_lm_decode_step_paged_logit_exact_vs_oracle():
    """The acceptance gate: decode through serve/ must be logit-exact vs
    the unfused f32-KV oracle at the planner-chosen widths."""
    cfg = get_smoke_config("qwen2-1.5b")
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    kv_state = lm.init_paged_state(cfg, n_pages=10, page_size=4)
    plan = plan_attention(32, 4)
    _, bucket = plan.bucket_for(9)
    rng = np.random.RandomState(6)
    # two sequences at different positions (continuous batch), prefilled
    for pages, n in (([1, 2], 7), ([3], 2)):
        toks = jnp.asarray([rng.randint(0, cfg.vocab_size, n)], jnp.int32)
        pg_ids = jnp.asarray(pages, jnp.int32)
        _, kv_state = lm.paged_prefill(params, toks, kv_state, pg_ids,
                                       pg_ids, 0, n, cfg,
                                       kv_fmt=FP8_152, acc=bucket.acc)
    pt = jnp.asarray([[1, 2, 0], [3, 4, 0]], jnp.int32)
    positions = jnp.asarray([7, 2], jnp.int32)
    tokens = jnp.asarray([[5], [11]], jnp.int32)
    kw = dict(kv_fmt=FP8_152, acc=bucket.acc)
    logits_k, kv_k = lm.paged_decode(
        params, tokens, kv_state, pt, positions, positions + 1, cfg, **kw)
    logits_o, kv_o = lm.paged_decode(
        params, tokens, kv_state, pt, positions, positions + 1, cfg,
        oracle=True, **kw)
    np.testing.assert_array_equal(np.asarray(logits_k), np.asarray(logits_o))
    for key in kv_k:
        np.testing.assert_array_equal(np.asarray(kv_k[key]),
                                      np.asarray(kv_o[key]))


def test_encdec_decode_step_paged():
    cfg = get_smoke_config("seamless-m4t-large-v2")
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.RandomState(7)
    b, enc_len = 2, 6
    frames = jnp.asarray(rng.standard_normal(
        (b, enc_len, cfg.frontend_dim)).astype(np.float32))
    enc_out = encdec.encode(params, frames, cfg, lm.L.LOCAL, remat=False)
    state = encdec.init_decode_state(cfg, b, 8, enc_len)
    state = encdec.prime_cross_attention(params, enc_out, cfg, state)
    kv_state = encdec.init_paged_state(cfg, n_pages=8, page_size=4)
    pt = jnp.asarray([[1, 0], [2, 0]], jnp.int32)
    positions = jnp.asarray([0, 0], jnp.int32)
    tokens = jnp.asarray([[3], [9]], jnp.int32)
    kw = dict(kv_fmt=FP8_152, acc=ACC)
    lk, kv_k = encdec.paged_decode(
        params, tokens, kv_state, state["xk"], state["xv"], pt, positions,
        positions + 1, cfg, **kw)
    lo, _ = encdec.paged_decode(
        params, tokens, kv_state, state["xk"], state["xv"], pt, positions,
        positions + 1, cfg, oracle=True, **kw)
    np.testing.assert_array_equal(np.asarray(lk), np.asarray(lo))
    assert np.all(np.isfinite(np.asarray(lk)))


# --------------------------------------------------------------------------
# the arena updated in place: bitwise what per-layer slabs gave
# --------------------------------------------------------------------------


def _paged_entry(path, cfg, params, acc):
    """One paged entry's call as ``fn(kv) -> (logits, kv)`` on the arena
    ``_layered_arena`` fills: sequence 0 holds 8 tokens on pages 1-2,
    sequence 1 holds 2 on page 3.  Decode and verify write at positions
    8 and 2 (page 5's first slot, which fixes its scale; mid-page 3, and
    on into page 6 under verify); prefill runs a 3-token slab at offset 8
    on page 5, as a prompt's last slab (logits) or as one before it."""
    kw = dict(kv_fmt=FP8_152, acc=acc)
    pt = jnp.asarray([[1, 2, 5], [3, 6, 0]], jnp.int32)
    pos = jnp.asarray([8, 2], jnp.int32)
    if path in ("decode", "decode_oracle"):
        toks = jnp.asarray([[5], [11]], jnp.int32)
        return lambda kv: lm.paged_decode(
            params, toks, kv, pt, pos, pos + 1, cfg,
            oracle=path == "decode_oracle", **kw)
    if path == "verify":
        toks = jnp.asarray([[5, 7, 9], [11, 13, 2]], jnp.int32)
        return lambda kv: lm.paged_verify(params, toks, kv, pt, pos,
                                          pos + 1, cfg, **kw)
    toks = jnp.asarray([[5, 7, 9, 0]], jnp.int32)
    row = jnp.asarray([1, 2, 5, 0], jnp.int32)
    return lambda kv: lm.paged_prefill(
        params, toks, kv, row, jnp.asarray([5], jnp.int32), 8, 3, cfg,
        want_logits=path == "prefill_final", **kw)


def _layered_arena(cfg, params, acc):
    rng = np.random.RandomState(21)
    kv = lm.init_paged_state(cfg, n_pages=8, page_size=4)
    for pages, n in (([1, 2], 8), ([3], 2)):
        toks = jnp.asarray([rng.randint(0, cfg.vocab_size, n)], jnp.int32)
        ids = jnp.asarray(pages, jnp.int32)
        _, kv = lm.paged_prefill(params, toks, kv, ids, ids, 0, n, cfg,
                                 kv_fmt=FP8_152, acc=acc)
    return kv


@pytest.mark.parametrize("path", ["decode", "decode_oracle", "verify",
                                  "prefill_final", "prefill_slab"])
def test_paged_entry_in_place_matches_layer_slabs(smoke_model, monkeypatch,
                                                  path):
    """Each paged entry writes and reads the whole arena at each layer's
    index: its logits and every arena byte are those of the same entry
    run over per-layer slabs (``conftest.layer_sliced_arena``)."""
    model, params = smoke_model
    cfg = model.cfg
    assert cfg.n_layers > 1, "one layer cannot tell the layers apart"
    _, bucket = plan_attention(28, 4).bucket_for(12)
    kv0 = _layered_arena(cfg, params, bucket.acc)
    logits, kv = jax.jit(_paged_entry(path, cfg, params, bucket.acc))(kv0)
    layer_sliced_arena(monkeypatch.setattr)
    want, kv_want = jax.jit(_paged_entry(path, cfg, params, bucket.acc))(kv0)
    assert (logits is None) == (path == "prefill_slab") == (want is None)
    if want is not None:
        np.testing.assert_array_equal(np.asarray(logits), np.asarray(want))
    for key in kv0:
        np.testing.assert_array_equal(np.asarray(kv[key]),
                                      np.asarray(kv_want[key]))
    assert not np.array_equal(np.asarray(kv["k"]), np.asarray(kv0["k"]))


# --------------------------------------------------------------------------
# serve-time VRR monitor
# --------------------------------------------------------------------------


def test_monitor_flags_underprovisioned_width():
    """A deliberately-too-narrow carry over a long context must show a
    measured swamp rate far above the planner width's (the monitor's
    breach signal; the one-sided knee test cannot see carry NOISE — see
    scheduler docstring)."""
    rng = np.random.RandomState(8)
    n = 16 * 24  # 24 pages
    arena, pt, lens = _filled_arena(rng, seq_tokens=(n,), n_pages=26,
                                    page_size=16)
    kv_state = {k: v[None] for k, v in arena.items()}
    plan = plan_attention(n, 16)
    _, bucket = plan.bucket_for(n)
    key = jax.random.PRNGKey(0)
    cfg = get_smoke_config("qwen2-1.5b")
    stats_bad = measure_decode_vrr(kv_state, np.asarray(pt[0]), n, cfg=cfg,
                                   kv_fmt=FP8_152, acc=(6, 1), key=key)
    assert float(stats_bad.swamp_rate) >= 0.15
    stats_ok = measure_decode_vrr(kv_state, np.asarray(pt[0]), n, cfg=cfg,
                                  kv_fmt=FP8_152, acc=bucket.acc, key=key)
    assert float(stats_ok.swamp_rate) < 0.15


def test_engine_monitor_rebuckets_on_breach(smoke_model):
    """An engine forced onto a 1-bit carry must emit a rebucket event and
    widen the plan mid-serve."""
    from repro.serve.plan import AttnBucket, AttnPlan

    model, params = smoke_model
    narrow = AttnPlan(page_size=4, m_p=5,
                      buckets=(AttnBucket(max_ctx=92, e_acc=6, m_acc=1),))
    eng = _engine(model, params, plan=narrow, monitor_cadence=2)
    eng.submit(list(range(1, 30)), 8)
    eng.run()
    rebuckets = [e for e in eng.events if e["event"] == "rebucket"]
    assert rebuckets, f"no rebucket event in {eng.events}"
    assert eng.plan.buckets[0].m_acc > 1


# --------------------------------------------------------------------------
# scheduler
# --------------------------------------------------------------------------


def _engine(model, params, **kw):
    kw.setdefault("n_pages", 24)
    kw.setdefault("page_size", 4)
    kw.setdefault("max_batch", 4)
    return ServeEngine(model, params, **kw)


@pytest.fixture(scope="module")
def smoke_model():
    cfg = get_smoke_config("qwen2-1.5b")
    model = get_model(cfg)
    return model, model.init_params(jax.random.PRNGKey(0))


def test_engine_continuous_batching_and_accounting(smoke_model):
    model, params = smoke_model
    eng = _engine(model, params)
    rng = np.random.RandomState(9)
    rids = [eng.submit(list(rng.randint(0, model.cfg.vocab_size, n)), 4)
            for n in (5, 9, 3)]
    results = eng.run()
    assert set(results) == set(rids)
    assert all(len(results[r]) == 4 for r in rids)
    assert eng.max_concurrent >= 3  # admitted together, decoded together
    eng.pool.check_invariants()
    assert eng.pool.free_pages == eng.pool.n_pages - 1  # all evicted


def test_engine_isolation_and_oracle_parity(smoke_model):
    """No cross-sequence reads: a sequence decodes the same tokens alone as
    inside a mixed continuous batch; and the whole engine is token-exact
    under the unfused-oracle attention."""
    model, params = smoke_model
    rng = np.random.RandomState(10)
    prompts = [list(rng.randint(0, model.cfg.vocab_size, n))
               for n in (5, 9, 3)]

    def run(oracle, subset):
        eng = _engine(model, params, oracle=oracle)
        rids = [eng.submit(prompts[i], 5) for i in subset]
        out = eng.run()
        return [tuple(out[r]) for r in rids]

    together = run(False, [0, 1, 2])
    assert run(False, [1])[0] == together[1]
    assert run(True, [0, 1, 2]) == together


def test_engine_admission_waits_for_pages(smoke_model):
    model, params = smoke_model
    eng = _engine(model, params, n_pages=7, page_size=4, max_batch=4)
    # capacity 6 pages = 24 tokens; three requests cannot all be resident
    rids = [eng.submit(list(range(1, 9)), 6) for _ in range(3)]
    results = eng.run()
    assert set(results) == set(rids)
    assert all(len(results[r]) == 6 for r in rids)
    eng.pool.check_invariants()
    assert eng.pool.free_pages == eng.pool.n_pages - 1


def test_engine_chunked_prefill_matches_oneshot(smoke_model):
    """The whole engine, chunked: slab-interleaved prefill must produce
    token-for-token the same generations as one-shot prefill (the
    scheduling changed; the numerics may not)."""
    model, params = smoke_model
    rng = np.random.RandomState(17)
    prompts = [list(rng.randint(0, model.cfg.vocab_size, n))
               for n in (9, 5, 3)]

    def run(chunk):
        eng = _engine(model, params, prefill_chunk_tokens=chunk)
        rids = [eng.submit(p, 4) for p in prompts]
        out = eng.run()
        return [tuple(out[r]) for r in rids], eng

    one, _ = run(None)
    for chunk in (4, 8):
        chunked, eng = run(chunk)
        assert chunked == one, f"chunk={chunk} changed the token streams"
        assert eng.prefill_slabs > len(prompts), "slabs did not split"
    eng.pool.check_invariants()


def test_engine_preemption_recompute_free(smoke_model):
    """Forcing preemption/swap through a tiny pool must not change a
    single generated token vs an unpressured run — restore is a
    byte-identical page copy, never a recompute."""
    model, params = smoke_model
    rng = np.random.RandomState(18)
    prompts = [list(rng.randint(0, model.cfg.vocab_size, 8))
               for _ in range(3)]

    def run(n_pages):
        eng = _engine(model, params, n_pages=n_pages, page_size=4,
                      max_batch=4, prefill_chunk_tokens=4)
        rids = [eng.submit(p, 6) for p in prompts]
        out = eng.run()
        eng.pool.check_invariants()
        assert eng.pool.free_pages == eng.pool.n_pages - 1
        return [tuple(out[r]) for r in rids], eng

    roomy, eng_roomy = run(32)
    tight, eng_tight = run(7)  # 6 usable pages for 3 x (8+6)-token requests
    assert eng_roomy.preemptions == 0
    assert eng_tight.preemptions > 0 and eng_tight.restores > 0, \
        "tiny pool failed to force the swap path"
    assert tight == roomy, "preemption/swap changed generated tokens"
    assert len(eng_tight.store) == 0


def test_engine_forced_preempt_midstream_is_exact(smoke_model):
    """Public preempt() at an arbitrary decode point, real model: the
    restored sequence continues exactly (swap is recompute-free)."""
    model, params = smoke_model
    rng = np.random.RandomState(19)
    prompt = list(rng.randint(0, model.cfg.vocab_size, 9))

    eng0 = _engine(model, params)
    r0 = eng0.submit(prompt, 6)
    baseline = eng0.run()[r0]

    eng = _engine(model, params)
    rid = eng.submit(prompt, 6)
    for _ in range(3):
        eng.step()
    assert rid in eng.active and len(eng.active[rid].generated) >= 2
    eng.preempt(rid)
    assert rid in eng.swapped and rid in eng.store
    out = eng.run()
    assert out[rid] == baseline
    assert eng.restores == 1


@pytest.mark.parametrize("scenario", ["chunked_prefill", "swap"])
def test_engine_in_place_arena_matches_layer_slabs(smoke_model, monkeypatch,
                                                   scenario):
    """The engine over the in-place arena, with chunked prefill (final and
    earlier slabs) or with preemption, swap-out and swap-in forced by a
    tight pool, gives the token streams, arena bytes and next decode
    logits that the same engine gives over per-layer slabs."""
    model, params = smoke_model
    rng = np.random.RandomState(23)
    lens = (9, 5, 3) if scenario == "chunked_prefill" else (8, 8, 8)
    prompts = [list(rng.randint(0, model.cfg.vocab_size, n)) for n in lens]
    n_pages = 24 if scenario == "chunked_prefill" else 7

    def run():
        eng = _engine(model, params, n_pages=n_pages,
                      prefill_chunk_tokens=4)
        rids = [eng.submit(p, 6) for p in prompts]
        out = eng.run()
        arena = {k: np.asarray(a) for k, a in eng.kv.items()}
        _, bucket = eng.plan.bucket_for(12)
        logits = eng.executor.decode_logits(DecodeRequest(
            rids=(0,), last_tokens=(5,), page_table=((1, 2, 3),),
            positions=(10,), seq_lens=(11,), acc=bucket.acc))
        return [tuple(out[r]) for r in rids], arena, np.asarray(logits), eng

    streams, arena, logits, eng = run()
    if scenario == "swap":
        assert eng.preemptions > 0 and eng.restores > 0
    else:
        assert eng.prefill_slabs > len(prompts)
    layer_sliced_arena(monkeypatch.setattr)
    want_streams, want_arena, want_logits, _ = run()
    assert streams == want_streams
    for key in arena:
        np.testing.assert_array_equal(arena[key], want_arena[key])
    np.testing.assert_array_equal(logits, want_logits)


def _aliased_params(compiled) -> set[int]:
    """Parameter numbers of a compiled program that its outputs alias."""
    head = compiled.as_text().splitlines()[0]
    alias = re.search(r"input_output_alias=\{(.*?)\}, entry", head)
    return set() if alias is None else {
        int(n) for n in re.findall(r"\}: \((\d+),", alias.group(1))}


@pytest.mark.parametrize("kind", ["decode", "prefill", "verify", "rollback"])
def test_executor_programs_alias_the_arena(smoke_model, kind):
    """Every program that advances the arena is compiled with the arena
    donated: its outputs alias exactly the arena's four input buffers."""
    model, params = smoke_model
    eng = _engine(model, params, prefill_chunk_tokens=4)
    ex, b = eng.executor, eng.plan.buckets[0]
    w, mb, z = b.max_pages(eng.page_size), ex.max_batch, jnp.zeros
    i32 = jnp.int32
    first = len(jax.tree.leaves(ex.params)) + 1  # params, tokens, arena
    if kind == "decode":
        low = ex._decode_fn(b.acc).lower(
            ex.params, z((mb, 1), i32), ex.kv, z((mb, w), i32),
            z((mb,), i32), z((mb,), i32))
    elif kind == "prefill":
        call = eng.plan.kernel_call(0, h=eng.cfg.n_heads,
                                    dh=eng.cfg.head_dim, kv_fmt=eng.kv_fmt,
                                    slab_tokens=4)
        low = ex._prefill_fn(b.acc, True, call).lower(
            ex.params, z((1, 4), i32), ex.kv, z((w,), i32), z((1,), i32),
            i32(0), i32(4))
    elif kind == "verify":
        low = ex._verify_fn(b.acc, 3).lower(
            ex.params, z((mb, 3), i32), ex.kv, z((mb, w), i32),
            z((mb,), i32), z((mb,), i32))
    else:
        first = 0
        low = ex._jit(("rollback", 2), KV.truncate_pages).lower(
            ex.kv, z((2,), i32), i32(0), i32(0))
    compiled = low.compile()
    assert _aliased_params(compiled) == set(range(first, first + 4))
    assert compiled.memory_analysis().alias_size_in_bytes == sum(
        a.nbytes for a in ex.kv.values())


def test_warmup_keeps_every_live_page(smoke_model):
    """Warm-up calls donate the arena and keep what they return: every
    page but the null page 0 is byte-identical afterwards, the arena
    they were given is gone, and the engine serves on without touching
    a deleted buffer."""
    model, params = smoke_model
    rng = np.random.RandomState(24)
    eng = _engine(model, params, prefill_chunk_tokens=4)
    eng.submit(list(rng.randint(0, model.cfg.vocab_size, 9)), 3)
    eng.run()
    # read through copies: a host view of a CPU buffer would pin it, and
    # a pinned buffer is copied rather than donated
    before = {k: np.asarray(jnp.copy(a)) for k, a in eng.kv.items()}
    assert before["k"][:, 1:].any() and before["k_se"][:, 1:].any()
    given = eng.executor.kv
    eng.executor.warmup(eng.plan, prefill_chunk=4)
    eng.executor.warmup_verify(eng.plan, k=2)
    assert all(a.is_deleted() for a in given.values())
    for key, a in eng.kv.items():
        np.testing.assert_array_equal(np.asarray(a)[:, 1:],
                                      before[key][:, 1:])
    rid = eng.submit(list(rng.randint(0, model.cfg.vocab_size, 6)), 4)
    assert len(eng.run()[rid]) == 4


def test_monitor_rebucket_keyed_by_grown_context(smoke_model):
    """Regression: the monitor must key its re-bucket on the GROWN
    (post-decode) context length.  A prompt admitted in bucket 0 that
    decodes past the bucket edge breaches in bucket 1 — bucket 1 must be
    the one widened, and bucket 0 (the original prompt length's bucket)
    must be left untouched (a prompt-length-keyed monitor would bump
    bucket 0 and, via monotonicity, drag bucket 1 with it)."""
    from repro.serve.plan import AttnBucket, AttnPlan

    model, params = smoke_model
    narrow = AttnPlan(page_size=4, m_p=5, buckets=(
        AttnBucket(max_ctx=8, e_acc=6, m_acc=1),
        AttnBucket(max_ctx=92, e_acc=6, m_acc=1)))
    eng = _engine(model, params, plan=narrow, monitor_cadence=4)
    eng.submit(list(range(1, 7)), 34)   # prompt 6 (bucket 0), grows past 8
    eng.run()
    probes = [e for e in eng.events if e.get("gemm") == "attn_decode"]
    assert probes and all(e["ctx"] > 8 and e["bucket"] == 1 for e in probes), \
        f"probes must land in the grown context's bucket: {probes}"
    rebuckets = [e for e in probes if e["event"] == "rebucket"]
    assert rebuckets, f"no rebucket despite the 1-bit carry: {probes}"
    assert eng.plan.buckets[1].m_acc > 1, "grown bucket was not widened"
    assert eng.plan.buckets[0].m_acc == 1, (
        "bucket 0 was bumped — the monitor keyed by the original prompt "
        "length instead of the grown context")


def test_serve_restore_honors_precision_schedule(tmp_path):
    """Satellite: restoring a checkpoint for serving must reproduce the
    recorded precision_schedule instead of re-deriving the default plan."""
    from repro.core.policy import AccumulationPolicy, plan_for_model
    from repro.launch.serve import _restore_params
    from repro.train.checkpoint import save_checkpoint

    policy = AccumulationPolicy(mode="predicted", chunk=64)
    cfg = plan_for_model(get_smoke_config("qwen2-1.5b"), seq_len=32,
                        global_batch=2, policy=policy)
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    save_checkpoint(str(tmp_path), 3, {"params": params},
                    precision_schedule={"mlp_up:fwd": 9})
    cfg2, model2, params2 = _restore_params(
        str(tmp_path), cfg, policy, model, params,
        seq_len=32, global_batch=2)
    assert cfg2.quant.mlp_up.fwd.m_acc == 9
    # un-scheduled GEMMs keep the solver plan
    assert cfg2.quant.attn_qkv.fwd.m_acc == cfg.quant.attn_qkv.fwd.m_acc
    np.testing.assert_array_equal(
        np.asarray(params2["embed"]), np.asarray(params["embed"]))


def test_pagepool_deterministic_invariants():
    pool = KV.PagePool(10, 4)
    a = pool.allocate(1, 6)   # 2 pages
    assert 0 not in a
    pool.allocate(2, 1)
    assert pool.pages_for(6) == 2 and pool.seq_len(1) == 6
    pool.extend(1, 2)         # 6 -> 8 tokens, still 2 pages
    assert len(pool.pages(1)) == 2
    pool.extend(1)            # 9 tokens -> 3rd page
    assert len(pool.pages(1)) == 3
    pool.check_invariants()
    pool.release(1)
    pool.check_invariants()
    assert pool.free_pages == 8
    with pytest.raises(ValueError):
        pool.allocate(2, 1)   # double allocate
    pool.release(2)
    assert pool.free_pages == 9


def test_pagepool_property_no_leaks_random_orders():
    hyp = pytest.importorskip("hypothesis", reason="needs `pip install -e .[test]`")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=50, deadline=None)
    @given(st.lists(st.tuples(st.integers(1, 30), st.integers(0, 20)),
                    min_size=1, max_size=12),
           st.randoms(use_true_random=False))
    def prop(jobs, rnd):
        pool = KV.PagePool(16, 4)
        live: list[int] = []
        for sid, (n_tokens, grow) in enumerate(jobs):
            # random completions first — eviction interleaves with admission
            while live and rnd.random() < 0.4:
                pool.release(live.pop(rnd.randrange(len(live))))
                pool.check_invariants()
            if pool.can_admit(n_tokens):
                pool.allocate(sid, n_tokens)
                live.append(sid)
                for _ in range(grow):
                    if pool.can_extend(sid):
                        pool.extend(sid)
                pool.check_invariants()
        for sid in live:
            pool.release(sid)
        pool.check_invariants()
        assert pool.free_pages == pool.n_pages - 1

    prop()


@pytest.mark.slow  # each example re-jits prefill/decode for its shapes
def test_engine_property_random_arrivals(smoke_model):
    hyp = pytest.importorskip("hypothesis", reason="needs `pip install -e .[test]`")
    from hypothesis import given, settings, strategies as st

    model, params = smoke_model

    @settings(max_examples=5, deadline=None)
    @given(st.lists(st.tuples(st.integers(1, 10), st.integers(1, 4)),
                    min_size=1, max_size=5))
    def prop(reqs):
        eng = _engine(model, params, n_pages=16, page_size=4, max_batch=3)
        rng = np.random.RandomState(0)
        rids = [eng.submit(list(rng.randint(0, model.cfg.vocab_size, n)), g)
                for n, g in reqs]
        out = eng.run()
        assert set(out) == set(rids)
        for rid, (_, g) in zip(rids, reqs):
            assert len(out[rid]) == g
        eng.pool.check_invariants()
        assert eng.pool.free_pages == eng.pool.n_pages - 1

    prop()
