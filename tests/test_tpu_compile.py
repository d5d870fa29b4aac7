"""Compile rehearsal: the main-path Pallas kernels compiled (not interpreted)
for a described TPU v5e chip, at the widths ``chip_smoke.py`` runs.

Nothing runs and no chip is needed: the TPU compiler refuses here what it
would refuse on the chip — block shapes off the (8, 128) tiling, scoped
VMEM overruns, the scalar-prefetch page gathers, Mosaic lowerings that the
interpreter never exercises.  The topology is described inside a fixture
(only the worker that runs this file loads the TPU compiler); where it
cannot be described the tests skip.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.policy import AccumulationPolicy, plan_for_model
from repro.kernels.attention import flash_prefill_paged, paged_attn_decode
from repro.kernels.bwd_pair import qmatmul_bwd_pair, qmatmul_bwd_pair_nsplit
from repro.kernels.fused import qmatmul_fused
from repro.kernels.ops import pair_n_segments
from repro.quant.formats import FP8_152, FPFormat
from repro.serve.plan import plan_attention

# chip_smoke.py's shapes: training qwen2-0.5b on 8 x 512 tokens, serving
# qwen2-1.5b from a 257-page arena of 16-token pages in 256-token slabs
TRAIN_ARCH, TRAIN_TOKENS = "qwen2-0.5b", 8 * 512
SERVE_ARCH, SERVE_TOKENS = "qwen2-1.5b", 2048
PAGE, N_PAGES, SLAB, DECODE_ROWS = 16, 257, 256, 8
KV_FMT = FPFormat(e=5, m=2)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # libtpu would otherwise write its logs under /tmp, outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but never read back here: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "kernel was not compiled by Mosaic"


def _plan(arch: str, tokens: int):
    return plan_for_model(get_config(arch), seq_len=tokens, global_batch=1,
                          policy=AccumulationPolicy(mode="predicted")).quant


# ------------------------------ dense GEMMs --------------------------------

F32, I8 = jnp.float32, jnp.int8


@pytest.mark.parametrize("case", ["train_mlp_up", "train_lm_head",
                                  "serve_mlp_up_sr"])
def test_qmatmul_fused_compiles(one_chip, case):
    if case == "train_mlp_up":   # forward + packed residual epilogue
        cfg, q = get_config(TRAIN_ARCH), _plan(TRAIN_ARCH, TRAIN_TOKENS)
        m, k, n, p = TRAIN_TOKENS, cfg.d_model, cfg.d_ff, q.mlp_up.fwd
        kw = dict(repr_fmt=FP8_152, return_quantized=True,
                  pack_residuals=True)
    elif case == "train_lm_head":  # (1, 6, 9) head over the whole vocab
        cfg, q = get_config(TRAIN_ARCH), _plan(TRAIN_ARCH, TRAIN_TOKENS)
        m, k, n, p = TRAIN_TOKENS, cfg.d_model, cfg.vocab_size, q.lm_head.fwd
        kw = {}
    else:                        # serving GEMM, stochastic-rounding carry
        cfg, q = get_config(SERVE_ARCH), _plan(SERVE_ARCH, SERVE_TOKENS)
        m, k, n, p = SERVE_TOKENS, cfg.d_model, cfg.d_ff, q.mlp_up.fwd
        kw = dict(repr_fmt=FP8_152, rounding="sr", sr_seed=3)
    _compile(lambda a, b: qmatmul_fused(
        a, b, e_acc=p.e_acc, m_acc=p.m_acc, block_k=p.chunk,
        interpret=False, **kw), one_chip, ((m, k), F32), ((k, n), F32))


@pytest.mark.parametrize("case", ["mlp_up", "mlp_up_stats", "lm_head_nsplit"])
def test_qmatmul_bwd_pair_compiles(one_chip, case):
    cfg, q = get_config(TRAIN_ARCH), _plan(TRAIN_ARCH, TRAIN_TOKENS)
    t = TRAIN_TOKENS
    qc = q.lm_head if case == "lm_head_nsplit" else q.mlp_up
    k, n = cfg.d_model, (cfg.vocab_size if case == "lm_head_nsplit"
                         else cfg.d_ff)
    kw = dict(repr_fmt=qc.repr_fmt, bwd_acc=(qc.bwd.e_acc, qc.bwd.m_acc),
              grad_acc=(qc.grad.e_acc, qc.grad.m_acc),
              block_t=qc.grad.chunk, block_n=qc.bwd.chunk,
              packed=qc.packs, interpret=False)
    res = I8 if qc.packs else F32
    shapes = (((t, n), F32), ((t, k), res), ((k, n), res))
    if case == "lm_head_nsplit":
        segs = pair_n_segments(qc, t, k, n)
        assert segs > 1, "the vocab-wide dw slab should need the N split"
        _compile(lambda g, x, w: qmatmul_bwd_pair_nsplit(
            g, x, w, n_split=segs, **kw), one_chip, *shapes)
    else:
        _compile(lambda g, x, w: qmatmul_bwd_pair(
            g, x, w, collect_stats=case == "mlp_up_stats", **kw),
            one_chip, *shapes)


# ------------------------------- attention ---------------------------------


def _arena_shapes(arch):
    cfg = get_config(arch)
    pages = ((N_PAGES, cfg.n_kv_heads, PAGE, cfg.head_dim), I8)
    scales = ((N_PAGES,), jnp.int32)
    return cfg, [pages, pages, scales, scales]


@pytest.mark.parametrize("arch", [SERVE_ARCH, TRAIN_ARCH])
def test_flash_prefill_paged_compiles(one_chip, arch):
    cfg, arena = _arena_shapes(arch)
    plan = plan_attention((N_PAGES - 1) * PAGE, PAGE,
                          prefill_chunk_tokens=SLAB)
    last = len(plan.buckets) - 1
    call = plan.kernel_call(last, h=cfg.n_heads, dh=cfg.head_dim,
                            kv_fmt=KV_FMT, slab_tokens=SLAB)
    i32 = ((), jnp.int32)
    _compile(lambda q, kp, vp, ks, vs, row, q0, ql, kl: flash_prefill_paged(
        q, kp, vp, ks, vs, row, q0, ql, kl, call=call, interpret=False),
        one_chip, ((SLAB, cfg.n_heads, cfg.head_dim), F32), *arena,
        ((call.max_pages,), jnp.int32), i32, i32, i32)


# the serve.qwen2-1.5b.decode64 cell: 64 rows at the 4,096-token bucket's
# 256-page table against its 8,193-page arena
CELL_ROWS, CELL_PAGES, CELL_WIDTH = 64, 8193, 256


@pytest.mark.parametrize("case", [SERVE_ARCH, TRAIN_ARCH, "decode64",
                                  "decode64_carry"])
def test_paged_attn_decode_compiles(one_chip, case):
    arch = TRAIN_ARCH if case == TRAIN_ARCH else SERVE_ARCH
    cfg, arena = _arena_shapes(arch)
    plan = plan_attention((N_PAGES - 1) * PAGE, PAGE)
    b = plan.buckets[-1]
    rows, width = DECODE_ROWS, b.max_pages(PAGE)
    if case.startswith("decode64"):
        rows, width = CELL_ROWS, CELL_WIDTH
        pages = ((CELL_PAGES, cfg.n_kv_heads, PAGE, cfg.head_dim), I8)
        scales = ((CELL_PAGES,), jnp.int32)
        arena = [pages, pages, scales, scales]
    _compile(lambda q, kp, vp, ks, vs, pt, sl: paged_attn_decode(
        q, kp, vp, ks, vs, pt, sl, kv_fmt=KV_FMT, acc=b.acc,
        return_carry=case.endswith("_carry"), interpret=False), one_chip,
        ((rows, cfg.n_heads, cfg.head_dim), F32), *arena,
        ((rows, width), jnp.int32), ((rows,), jnp.int32))


@pytest.mark.parametrize("case", ["decode", "decode_carry", "prefill"])
def test_kernels_read_the_layer_of_the_whole_arena(one_chip, case):
    """The serving programs hand the kernels the whole (L, P, KV, page,
    dh) arena of the decode64 cell and the layer as a traced scalar; the
    decode kernel's page DMAs and the prefill kernel's page blocks then
    index ``[layer, page]``."""
    cfg = get_config(SERVE_ARCH)
    pages = ((cfg.n_layers, CELL_PAGES, cfg.n_kv_heads, PAGE, cfg.head_dim),
             I8)
    scales = ((cfg.n_layers, CELL_PAGES), jnp.int32)
    arena = [pages, pages, scales, scales]
    i32 = ((), jnp.int32)
    plan = plan_attention((CELL_PAGES - 1) * PAGE, PAGE,
                          prefill_chunk_tokens=SLAB)
    if case == "prefill":
        last = len(plan.buckets) - 1
        call = plan.kernel_call(last, h=cfg.n_heads, dh=cfg.head_dim,
                                kv_fmt=KV_FMT, slab_tokens=SLAB)
        _compile(lambda q, kp, vp, ks, vs, ly, row, q0, ql, kl:
                 flash_prefill_paged(q, kp, vp, ks, vs, row, q0, ql, kl,
                                     call=call, layer=ly, interpret=False),
                 one_chip, ((SLAB, cfg.n_heads, cfg.head_dim), F32), *arena,
                 i32, ((call.max_pages,), jnp.int32), i32, i32, i32)
        return
    acc = plan.buckets[-1].acc
    _compile(lambda q, kp, vp, ks, vs, ly, pt, sl: paged_attn_decode(
        q, kp, vp, ks, vs, pt, sl, layer=ly, kv_fmt=KV_FMT, acc=acc,
        return_carry=case == "decode_carry", interpret=False), one_chip,
        ((CELL_ROWS, cfg.n_heads, cfg.head_dim), F32), *arena, i32,
        ((CELL_ROWS, CELL_WIDTH), jnp.int32), ((CELL_ROWS,), jnp.int32))
