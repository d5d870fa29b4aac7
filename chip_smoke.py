"""Smoke run of the training and serving paths on a TPU, with the paper's
quantized kernels compiled for the chip (never interpreted).

    python chip_smoke.py               # one chip: phases (a), (b), (c)
    python chip_smoke.py --four-chips  # four chips: tensor-parallel serving

One chip, one process (a chip belongs to one process), in this order:

(a) train   ``repro.launch.train.main`` on qwen2-0.5b at its published
            widths under ``--policy predicted``: a few steps, every loss
            finite, the compiled step holds the Pallas kernels.
(b) serve   ``repro.launch.serve.main`` on qwen2-1.5b at its published
            widths under ``--policy predicted`` with chunked prefill: four
            requests of ~100 to 2000 prompt tokens, 32 generated each; the
            compiled decode step holds the Pallas kernels.
(c) parity  ``qmatmul_fused`` against ``kernels/ref.py`` and
            ``paged_attn_decode`` against its jnp reference, at the widths
            of (a) and (b), oracles at ``highest`` matmul precision: the
            largest difference is at most one ulp of the accumulator format.

``--four-chips`` runs instead: qwen3-8b at full width served on four
tensor-parallel shards, and the sharded serving contract on a
layer-truncated qwen3-8b at full width that one chip holds (1-shard and
4-shard engines: equal token streams, bitwise-equal arenas and decode
logits, under the exact and the quantized dense plans).

Weights are random, made from a seed.  The script exits non-zero before any
work unless JAX's first device is a TPU, or when the ``repro`` package is not
beside it.  Its last line on stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
a failed phase prints no such line and exits 1.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
SEED = 0

# (a): a batch and sequence length whose weights, Adam state and
# activations fit one v5e chip's 16 GB (qwen2-1.5b does not: f32 params and
# Adam state alone are ~25 GB)
TRAIN = dict(arch="qwen2-0.5b", steps=4, batch=8, seq=512)
# (b): prompts spread over the attention buckets, slabs of 256 tokens; 257
# pages of 16 tokens hold all four contexts (4096-token pool)
SERVE = dict(arch="qwen2-1.5b", prompts=(100, 400, 1000, 2000), gen=32,
             page=16, pages=257, chunk=256, gemm_tokens=2048)
# --four-chips: the served model, and the layers of the parity replica
FOUR = dict(arch="qwen3-8b", prompts=(100, 400, 1000, 2000), gen=16,
            parity_layers=4, parity_prompts=(100, 400), shards=4)


def log(msg: str) -> None:
    print(msg, flush=True)


def has_kernels(compiled_text: str, what: str) -> None:
    """A program whose Pallas kernels ran compiled holds Mosaic custom
    calls; an interpreted kernel would have been inlined as plain HLO."""
    n = compiled_text.count("tpu_custom_call")
    if n == 0:
        raise RuntimeError(f"{what}: no tpu_custom_call in the compiled "
                           "program (kernels interpreted?)")
    log(f"{what}: {n} tpu_custom_call ops in the compiled program")


def acc_ulp_diff(got, want, m_acc: int):
    """Per-element |got - want| in ulps of a (1, e, m_acc) accumulator at
    the larger magnitude of the pair; 0 where both are 0."""
    import numpy as np

    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    mag = np.maximum(np.abs(got), np.abs(want))
    ulp = np.exp2(np.floor(np.log2(np.where(mag > 0, mag, 1.0))) - m_acc)
    return np.where(mag > 0, np.abs(got - want) / ulp, 0.0)


def report_parity(name: str, got, want, m_acc: int,
                  limit: float | None = 1.0) -> None:
    """Print the bitwise-equal share and the largest difference in ulps;
    fail above ``limit`` ulps (None: a reading only)."""
    import numpy as np

    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        raise RuntimeError(f"{name}: shape {got.shape} vs {want.shape}, "
                           "or non-finite output")
    equal = float(np.mean(got == want))
    worst = float(acc_ulp_diff(got, want, m_acc).max())
    log(f"parity {name}: bitwise-equal share {equal:.6f}, largest "
        f"difference {worst:.4f} ulp of the m_acc={m_acc} accumulator")
    if limit is not None and worst > limit:
        raise RuntimeError(f"{name}: {worst} ulp > {limit}")


# --------------------------------------------------------------------------
# (a) train
# --------------------------------------------------------------------------


def phase_train(jax) -> None:
    from repro.launch import train

    t0 = time.time()
    out = train.main([
        "--arch", TRAIN["arch"], "--policy", "predicted",
        "--steps", str(TRAIN["steps"]),
        "--global-batch", str(TRAIN["batch"]),
        "--seq-len", str(TRAIN["seq"]),
        "--warmup", "1", "--log-every", "1", "--seed", str(SEED)])
    losses = out["losses"]
    log(f"train {TRAIN['arch']}: losses {losses} ({time.time() - t0:.1f}s "
        "including compilation)")
    if len(losses) != TRAIN["steps"] or not all(map(math.isfinite, losses)):
        raise RuntimeError(f"train: expected {TRAIN['steps']} finite "
                           f"losses, got {losses}")
    has_kernels(out["step_fn"].lower(out["state"], out["batch"])
                .compile().as_text(), "train step")
    stats = jax.devices()[0].memory_stats() or {}
    log(f"train peak_bytes_in_use: {stats.get('peak_bytes_in_use')}")


# --------------------------------------------------------------------------
# (b) serve
# --------------------------------------------------------------------------


def serve_args(arch, prompts, gen, *extra) -> list[str]:
    return ["--arch", arch, "--policy", "predicted",
            "--prompt-lens", ",".join(map(str, prompts)),
            "--gen", str(gen), "--page-size", str(SERVE["page"]),
            "--pages", str(SERVE["pages"]),
            "--prefill-chunk", str(SERVE["chunk"]), "--seed", str(SEED),
            *extra]


def check_served(out, prompts, gen, what: str) -> None:
    results = out["results"]
    for rid, toks in sorted(results.items()):
        log(f"{what} request {rid} ({prompts[rid]} prompt tokens): {toks}")
    if len(results) != len(prompts) or any(
            len(t) != gen for t in results.values()):
        raise RuntimeError(f"{what}: expected {len(prompts)} requests of "
                           f"{gen} tokens")
    log(f"{what} compile cache: {out['compile_stats']}")


def decode_program(eng) -> str:
    """Compiled text of the engine's own decode step (the executor's
    memoized jit) at its widest bucket, over the engine's weights and
    arena."""
    import jax.numpy as jnp

    ex = eng.executor
    b = eng.plan.buckets[-1]
    mb, w = ex.max_batch, b.max_pages(eng.page_size)
    i32 = jnp.int32
    return ex._decode_fn(b.acc).lower(
        ex.params, jnp.zeros((mb, 1), i32), ex.kv, jnp.zeros((mb, w), i32),
        jnp.zeros((mb,), i32), jnp.zeros((mb,), i32)).compile().as_text()


def phase_serve(jax) -> None:
    from repro.launch import serve

    t0 = time.time()
    out = serve.main(serve_args(SERVE["arch"], SERVE["prompts"],
                                SERVE["gen"]))
    log(f"serve {SERVE['arch']}: {time.time() - t0:.1f}s including "
        "compilation")
    check_served(out, SERVE["prompts"], SERVE["gen"], "serve")
    has_kernels(decode_program(out["engine"]), "serve decode step")


# --------------------------------------------------------------------------
# (c) kernel parity
# --------------------------------------------------------------------------


def gemm_parity(jax, arch: str, tokens: int) -> None:
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.core.policy import AccumulationPolicy, plan_for_model
    from repro.kernels.fused import qmatmul_fused
    from repro.kernels.ref import ref_qmatmul
    from repro.quant.formats import FP8_152
    from repro.quant.qnum import quantize

    cfg = plan_for_model(get_config(arch), seq_len=tokens, global_batch=1,
                         policy=AccumulationPolicy(mode="predicted"))
    p = cfg.quant.mlp_up.fwd
    k, n = cfg.d_model, cfg.d_ff
    ka, kb = jax.random.split(jax.random.PRNGKey(SEED))
    a = jax.random.normal(ka, (tokens, k), jnp.float32)
    b = jax.random.normal(kb, (k, n), jnp.float32)
    kernel = jax.jit(lambda a, b: qmatmul_fused(
        a, b, repr_fmt=FP8_152, e_acc=p.e_acc, m_acc=p.m_acc,
        block_k=p.chunk))
    compiled = kernel.lower(a, b).compile()
    has_kernels(compiled.as_text(), f"qmatmul_fused {arch}")
    got = compiled(a, b)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda a, b: ref_qmatmul(
            quantize(a, FP8_152), quantize(b, FP8_152), e_acc=p.e_acc,
            m_acc=p.m_acc, block_k=p.chunk))(a, b)
    report_parity(f"qmatmul_fused {arch} mlp_up {tokens}x{k}x{n} "
                  f"acc(1,{p.e_acc},{p.m_acc}) n1={p.chunk}",
                  got, want, p.m_acc)


def decode_parity(jax) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.kernels.attention import (paged_attn_decode,
                                         paged_attn_decode_reference)
    from repro.quant.formats import FPFormat
    from repro.quant.qnum import quantize
    from repro.quant.qtensor import pack_block
    from repro.serve.plan import plan_attention

    cfg = get_config(SERVE["arch"])
    page, n_pages = SERVE["page"], SERVE["pages"]
    plan = plan_attention((n_pages - 1) * page, page)
    acc = plan.buckets[-1].acc
    kv_fmt = FPFormat(e=5, m=2)
    h, kvh, dh, b = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, 8
    width = plan.buckets[-1].max_pages(page)
    rng = np.random.RandomState(SEED)
    keys = jax.random.split(jax.random.PRNGKey(SEED), 3)

    def pages(key):
        x = jax.random.normal(key, (n_pages, kvh, page, dh), jnp.float32)
        return pack_block(quantize(x, kv_fmt), kv_fmt.e, kv_fmt.m)

    k_pages, v_pages = pages(keys[0]), pages(keys[1])
    k_se = jnp.asarray(rng.randint(-2, 3, n_pages), jnp.int32)
    v_se = jnp.asarray(rng.randint(-2, 3, n_pages), jnp.int32)
    # the serve path's queries are bf16 activations
    q = jax.random.normal(keys[2], (b, h, dh), jnp.float32).astype(
        jnp.bfloat16).astype(jnp.float32)
    seq_lens = jnp.asarray(
        rng.randint(1, width * page + 1, b), jnp.int32).at[0].set(
        width * page)
    table = jnp.asarray(np.stack([rng.permutation(np.arange(1, n_pages))
                                  [:width] for _ in range(b)]), jnp.int32)
    args = (q, k_pages, v_pages, k_se, v_se, table, seq_lens)
    kernel = jax.jit(lambda *a: paged_attn_decode(*a, kv_fmt=kv_fmt,
                                                  acc=acc))
    compiled = kernel.lower(*args).compile()
    has_kernels(compiled.as_text(), "paged_attn_decode")
    got = compiled(*args)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda *a: paged_attn_decode_reference(
            *a, kv_fmt=kv_fmt, acc=acc))(*args)
    name = (f"paged_attn_decode {SERVE['arch']} {h}q/{kvh}kv heads dh {dh}, "
            f"{b} rows up to {width} pages of {page} acc(1,{acc[0]},{acc[1]})")
    report_parity(name, got, want, acc[1])

    # a reading only: the kernel with its in-block contractions at the
    # backend's default precision, against the same oracle, says whether
    # attention.PV_PRECISION = HIGHEST is needed on this chip
    from repro.kernels import attention

    was = attention.PV_PRECISION
    attention.PV_PRECISION = jax.lax.Precision.DEFAULT
    jax.clear_caches()  # the kernel's inner jits would reuse the HIGHEST trace
    try:
        got = jax.jit(lambda *a: paged_attn_decode(*a, kv_fmt=kv_fmt,
                                                   acc=acc))(*args)
    finally:
        attention.PV_PRECISION = was
        jax.clear_caches()
    report_parity(name + ", kernel at DEFAULT precision", got, want, acc[1],
                  limit=None)


def phase_parity(jax) -> None:
    gemm_parity(jax, TRAIN["arch"], TRAIN["batch"] * TRAIN["seq"])
    gemm_parity(jax, SERVE["arch"], SERVE["gemm_tokens"])
    decode_parity(jax)


# --------------------------------------------------------------------------
# --four-chips: tensor-parallel serving
# --------------------------------------------------------------------------


def phase_four_serve(jax) -> None:
    from repro.launch import serve

    t0 = time.time()
    out = serve.main(serve_args(FOUR["arch"], FOUR["prompts"], FOUR["gen"],
                                "--serve-mesh", str(FOUR["shards"])))
    log(f"serve {FOUR['arch']} on {FOUR['shards']} shards: "
        f"{time.time() - t0:.1f}s including compilation")
    check_served(out, FOUR["prompts"], FOUR["gen"], "sharded serve")


def sharded_divergence(jax, mode: str) -> str | None:
    """1-shard against 4-shard serving of the layer-truncated replica under
    ``--policy mode``: None when bitwise equal, else what diverged."""
    import numpy as np

    from repro.configs import get_config
    from repro.core.policy import AccumulationPolicy, plan_for_model
    from repro.launch.serve import _init_params
    from repro.models.api import get_model
    from repro.quant.formats import FPFormat
    from repro.serve.plan import plan_attention
    from repro.serve.scheduler import sharded_divergence as divergence

    prompts, gen, page, n_pages = FOUR["parity_prompts"], 8, SERVE["page"], 65
    cfg = dataclasses.replace(get_config(FOUR["arch"]),
                              n_layers=FOUR["parity_layers"])
    cfg = plan_for_model(cfg, seq_len=max(prompts) + gen,
                         global_batch=len(prompts),
                         policy=AccumulationPolicy(mode=mode))
    model = get_model(cfg)
    params = _init_params(model, jax.random.PRNGKey(SEED))
    plan = plan_attention((n_pages - 1) * page, page,
                          prefill_chunk_tokens=SERVE["chunk"],
                          tp_shards=FOUR["shards"])
    rng = np.random.RandomState(SEED)
    reqs = [list(rng.randint(1, cfg.vocab_size, n)) for n in prompts]
    return divergence(model, params, reqs, plan, FOUR["shards"], gen=gen,
                      n_pages=n_pages, kv_fmt=FPFormat(e=5, m=2),
                      max_batch=4)


def phase_four_parity(jax) -> None:
    """The sharded serving contract on a layer-truncated replica at full
    width, under the exact and the quantized dense plans."""
    for mode in ("exact", "predicted"):
        diff = sharded_divergence(jax, mode)
        if diff is not None:
            raise RuntimeError(f"--policy {mode}: 1 vs {FOUR['shards']} "
                               f"shards diverged: {diff}")
        log(f"sharded parity {FOUR['arch']} x{FOUR['parity_layers']} layers "
            f"--policy {mode}, 1 vs {FOUR['shards']} shards: token streams "
            "equal, arenas and decode logits bitwise equal")


# --------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip tensor-parallel serving "
                         "path and its parity contract")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("chip_smoke: the repro package is not beside this script",
              file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    # libtpu would otherwise write its logs under /tmp, outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (first device: {dev.platform}"
              f"); nothing was run", file=sys.stderr)
        return 2
    count = len(jax.devices())
    want = FOUR["shards"] if args.four_chips else 1
    if count < want:
        print(f"chip_smoke: needs {want} chips, JAX finds {count}",
              file=sys.stderr)
        return 2

    from repro.kernels.autotune import DEFAULT_TABLE_PATH
    from repro.runtime import enable_compile_cache

    log(f"device: {dev.device_kind} x{count}; compile cache "
        f"{enable_compile_cache()}; autotune table {DEFAULT_TABLE_PATH}")
    phases = ([phase_four_serve, phase_four_parity] if args.four_chips
              else [phase_train, phase_serve, phase_parity])
    for phase in phases:
        t0 = time.time()
        log(f"=== {phase.__name__}")
        try:
            phase(jax)
        except Exception:
            traceback.print_exc()
            print(f"chip_smoke: {phase.__name__} failed", file=sys.stderr)
            return 1
        log(f"=== {phase.__name__} done in {time.time() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
