"""Serving driver: continuous batching over the paged QTensor KV-cache.

Attention-stack families (dense / moe / vlm) serve through
``repro.serve.ServeEngine`` — paged int8 KV pages, flash prefill/decode
kernels with planner-chosen accumulator widths, optimistic admission with
preemption/swap to a host-side store, chunked prefill slabs interleaved
with batched decode (``--prefill-chunk``), and page eviction on
completion — so requests of wildly different lengths share one arena and
one decode batch.  ``--reserve-admission`` restores the worst-case
reservation baseline (no preemption).  ``--spec-decode K`` turns on
speculative decoding: a smaller draft model (``--draft-config``)
proposes K tokens per round, one knee-certified batched verify GEMM
scores them, and rejections roll the paged KV back page-exactly — the
emitted streams stay bitwise identical to plain greedy decode.
Families the paged path does not cover (ssm / hybrid / encdec) fall
back to the legacy static-batch loop below.

Restoring from a training checkpoint honors the telemetry controller's
realized ``precision_schedule`` (recorded in ``meta.json``): the dense-GEMM
QuantPlan the run actually converged under is reproduced via
``apply_schedule`` instead of re-derived from the static policy.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --smoke \
      --prompt-lens 16,32,48 --gen 16
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config, get_smoke_config
from repro.core.policy import AccumulationPolicy, plan_for_model
from repro.data.pipeline import DataConfig, SyntheticLM, with_extras
from repro.launch.mesh import make_serve_mesh
from repro.models import encdec
from repro.models.api import get_model
from repro.models.layers import Dist
from repro.models.lm import PAGED_FAMILIES
from repro.runtime import enable_compile_cache


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--prompt-lens", default="",
                    help="comma-separated prompt lengths, one request each "
                         "(continuous batching); default: --batch copies of "
                         "--prompt-len")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--pages", type=int, default=0,
                    help="KV pool pages (0 = sized for the workload +25%%)")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked-prefill slab size in tokens (multiple of "
                         "--page-size; 0 = one-shot prefill per admission)")
    ap.add_argument("--reserve-admission", action="store_true",
                    help="worst-case page-reservation admission, no "
                         "preemption/swap (the pre-chunking baseline)")
    ap.add_argument("--spec-decode", type=int, default=0, metavar="K",
                    help="speculative decoding: a smaller draft model "
                         "proposes K tokens per round, one batched verify "
                         "GEMM scores them, rejection is a page-exact "
                         "rollback (0 = off).  Token streams stay bitwise "
                         "identical to plain greedy decode")
    ap.add_argument("--draft-config", default="qwen2-0.5b",
                    help="draft-model arch for --spec-decode (must share "
                         "the target's vocabulary)")
    ap.add_argument("--policy", choices=["exact", "predicted"], default="exact",
                    help="dense-GEMM accumulation plan for the serve path")
    ap.add_argument("--chunk", type=int, default=64)
    ap.add_argument("--v-hint", type=float, default=0.0,
                    help="certified per-term bound on the attention "
                         "accumulation (value magnitude x softmax weight) "
                         "used by the planner's e_acc sizing; 0 = the "
                         "historical default (repro.serve.plan."
                         "DEFAULT_V_HINT).  The serve monitor reports the "
                         "measured hint next to the planned one")
    ap.add_argument("--ckpt-dir", default="",
                    help="restore params (and the recorded precision "
                         "schedule) from the latest training checkpoint")
    ap.add_argument("--monitor-cadence", type=int, default=0,
                    help="decode steps between serve-time VRR probes")
    ap.add_argument("--serve-mesh", type=int, default=0,
                    help="tensor-parallel shard count for the serving mesh "
                         "(0 = single device).  Heads, d_ff and the KV "
                         "arena's kv-head axis split across shards; logits "
                         "stay bitwise the single-device logits")
    ap.add_argument("--logit-wire", choices=["gather", "int8"],
                    default="gather",
                    help="sharded unembed reduction: exact all_gather, or "
                         "the int8 compressed-psum wire (lossy in general)")
    ap.add_argument("--no-warmup", action="store_true",
                    help="skip the startup compile-cache warmup (every "
                         "bucket's kernels then compile lazily on first "
                         "traffic).  With --serve-mesh the skipped traces "
                         "are the sharded executables — first traffic then "
                         "pays the full shard_map compile, so keep warmup "
                         "on for latency-sensitive sharded serving")
    ap.add_argument("--legacy", action="store_true",
                    help="force the static-batch loop")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--obs-spans", default="",
                    help="trace the request lifecycle (repro.obs.trace) and "
                         "export the span tree as JSONL here")
    ap.add_argument("--obs-metrics", default="",
                    help="record engine metrics in the unified registry "
                         "(repro.obs.metrics) and export them as JSONL here")
    ap.add_argument("--obs-prometheus", default="",
                    help="also export the registry in Prometheus textfile-"
                         "collector format here")
    ap.add_argument("--events-capacity", type=int, default=4096,
                    help="ring-buffer capacity for engine events "
                         "(preempt/restore/monitor records; 0 = unbounded)")
    return ap.parse_args(argv)


def _restore_params(ckpt_dir: str, cfg, policy, model, params,
                    *, seq_len: int, global_batch: int):
    """Latest-checkpoint params + the precision schedule the run trained
    under (satellite: serve honors ``precision_schedule`` instead of
    re-deriving the default plan)."""
    from repro.train.checkpoint import latest_step, restore_checkpoint

    step = latest_step(ckpt_dir)
    if step is None:
        raise FileNotFoundError(f"no checkpoint under {ckpt_dir}")
    like = {"params": jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)}
    state, meta = restore_checkpoint(ckpt_dir, step, like)
    params = state["params"]
    schedule = meta.get("precision_schedule")
    if schedule:
        from repro.telemetry.controller import PrecisionController, apply_schedule

        ctl = PrecisionController(policy)
        ctl.restore_meta(schedule)
        cfg = apply_schedule(cfg, policy, ctl.schedule(),
                             seq_len=seq_len, global_batch=global_batch)
        model = get_model(cfg)
        print(f"restored step {step} with precision schedule {schedule}")
    else:
        print(f"restored step {step} (no precision schedule recorded)")
    return cfg, model, params


def _to_bf16(params):
    return jax.tree.map(
        lambda x: x.astype(jnp.bfloat16) if x.dtype == jnp.float32 else x,
        params)


def _init_params(model, key, *, mesh=None, logit_wire: str = "gather"):
    """Random serving weights, made in bf16 directly where they live: on
    the default device, or already sharded over the serve ``mesh`` — a
    model that fits only sharded never exists whole, nor in f32, on one
    device."""
    init = jax.jit(lambda k: _to_bf16(model.init_params(k)))
    if mesh is None:
        return init(key)
    from repro.sharding.specs import named_shardings, serve_param_specs

    specs = serve_param_specs(jax.eval_shape(init, key),
                              n_shards=mesh.shape["model"],
                              logit_wire=logit_wire)
    return jax.jit(init, out_shardings=named_shardings(specs, mesh))(key)


def main(argv=None) -> dict:
    args = parse_args(argv)
    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.prompt_lens:
        prompt_lens = [int(x) for x in args.prompt_lens.split(",")]
    else:
        prompt_lens = [args.prompt_len] * args.batch
    max_ctx = max(prompt_lens) + args.gen

    policy = AccumulationPolicy(mode=args.policy, chunk=args.chunk)
    cfg = plan_for_model(cfg, seq_len=max_ctx, global_batch=len(prompt_lens),
                         policy=policy)
    model = get_model(cfg)
    mesh = make_serve_mesh(args.serve_mesh) if args.serve_mesh else None
    if args.ckpt_dir:
        params = model.init_params(jax.random.PRNGKey(args.seed))
        cfg, model, params = _restore_params(
            args.ckpt_dir, cfg, policy, model, params,
            seq_len=max_ctx, global_batch=len(prompt_lens))
        params = _to_bf16(params)
    else:
        params = _init_params(model, jax.random.PRNGKey(args.seed),
                              mesh=mesh, logit_wire=args.logit_wire)

    if args.legacy or cfg.family not in PAGED_FAMILIES:
        return _legacy_main(args, cfg, model, params)

    from repro.serve.scheduler import ServeEngine

    tokens_needed = sum(pl + args.gen for pl in prompt_lens)
    n_pages = args.pages or (
        -(-int(tokens_needed * 1.25) // args.page_size) + 1)
    executor = None
    if args.serve_mesh:
        from repro.quant.formats import FPFormat
        from repro.serve.kvcache import PagedKVConfig
        from repro.serve.scheduler import ShardedModelExecutor

        pc = PagedKVConfig.for_model(cfg, n_pages=n_pages,
                                     page_size=args.page_size,
                                     kv_fmt=FPFormat(e=5, m=2))
        executor = ShardedModelExecutor(
            model, params, pc, kv_fmt=pc.kv_fmt, mesh=mesh,
            max_batch=args.max_batch, logit_wire=args.logit_wire)
        print(f"serve mesh: {executor.n_shards} tensor-parallel shards, "
              f"logit wire {args.logit_wire}")
    tracer = None
    if args.obs_spans:
        from repro.obs.trace import Tracer

        tracer = Tracer()
    registry = None
    if args.obs_metrics or args.obs_prometheus:
        from repro.obs.metrics import get_registry

        registry = get_registry()
    eng_kw = dict(n_pages=n_pages, v_hint=args.v_hint or None,
                  page_size=args.page_size, max_batch=args.max_batch,
                  prefill_chunk_tokens=args.prefill_chunk or None,
                  reserve_admission=args.reserve_admission,
                  monitor_cadence=args.monitor_cadence, seed=args.seed,
                  executor=executor, tracer=tracer, metrics=registry,
                  events_capacity=args.events_capacity or None)
    if args.spec_decode:
        if args.serve_mesh:
            raise SystemExit("--spec-decode does not compose with "
                             "--serve-mesh yet (single-shard only)")
        from repro.serve.spec import SpecDecodeEngine

        draft_cfg = (get_smoke_config(args.draft_config) if args.smoke
                     else get_config(args.draft_config))
        if draft_cfg.vocab_size != cfg.vocab_size:
            raise SystemExit(
                f"draft vocab {draft_cfg.vocab_size} != target vocab "
                f"{cfg.vocab_size}: verify compares token ids")
        draft_cfg = plan_for_model(draft_cfg, seq_len=max_ctx,
                                   global_batch=len(prompt_lens),
                                   policy=policy)
        draft_model = get_model(draft_cfg)
        draft_params = _to_bf16(
            draft_model.init_params(jax.random.PRNGKey(args.seed + 7)))
        eng = SpecDecodeEngine(model, params, spec_k=args.spec_decode,
                               draft_model=draft_model,
                               draft_params=draft_params, **eng_kw)
        print(f"speculative decoding: k={args.spec_decode} draft "
              f"{draft_cfg.name} ({args.draft_config})")
    else:
        eng = ServeEngine(model, params, **eng_kw)
    if not args.no_warmup:
        # compile every certified bucket's prefill/decode kernels BEFORE
        # traffic arrives — steady-state serving then performs zero traces
        t0 = time.time()
        warm = eng.warmup()
        print(f"warmup: {warm['compiles']} compiles across "
              f"{warm['buckets']} buckets in {time.time() - t0:.2f}s")
    rng = jax.random.PRNGKey(args.seed + 1)
    rids = []
    for pl_ in prompt_lens:
        rng, sub = jax.random.split(rng)
        prompt = jax.random.randint(sub, (pl_,), 0, cfg.vocab_size)
        rids.append(eng.submit([int(t) for t in prompt], args.gen))

    t0 = time.time()
    results = eng.run()
    dt = time.time() - t0
    toks_per_s = eng.decoded_tokens / max(dt, 1e-9)
    packed = eng.kv_bytes_per_token()
    f32 = eng.kv_bytes_per_token(carrier_bytes=4)
    print(f"arch={cfg.name} requests={len(rids)} "
          f"prompt_lens={prompt_lens} gen={args.gen}")
    print(f"continuous batching: {eng.decoded_tokens} tokens in {dt:.2f}s "
          f"({toks_per_s:.1f} tok/s), max concurrent {eng.max_concurrent}, "
          f"pool {n_pages} x {args.page_size}-token pages")
    print(f"scheduler: {eng.prefill_slabs} prefill slabs "
          f"(chunk={args.prefill_chunk or 'one-shot'}), "
          f"{eng.preemptions} preemptions / {eng.restores} restores, "
          f"utilization {eng.utilization():.3f} "
          f"({'reservation' if args.reserve_admission else 'optimistic'} "
          f"admission)")
    print(f"KV bytes/token: packed {packed:.1f} vs f32 {f32:.1f} "
          f"({f32 / packed:.2f}x)")
    if args.spec_decode:
        print(f"spec decode: {eng.spec_rounds} rounds, acceptance "
              f"{eng.acceptance_rate():.3f} "
              f"({eng.spec_accepted}/{eng.spec_proposed} draft tokens), "
              f"{eng.spec_emitted} tokens committed by verify, "
              f"{eng.spec_rollback_tokens} rolled back, "
              f"{eng.fallback_rows} plain-lane fallbacks")
    if eng.tp_shards > 1:
        print(f"per-shard KV bytes/token: "
              f"{eng.kv_bytes_per_token(per_shard=True):.1f} "
              f"across {eng.tp_shards} shards")
    cstats = eng.compile_stats()
    if cstats is not None:
        steady = cstats["compiles"] - cstats["warm_compiles"]
        print(f"compile cache: {cstats['compiles']} compiles "
              f"({cstats['warm_compiles']} at warmup, {steady} steady-state), "
              f"{cstats['hits']} dispatch hits / {cstats['misses']} misses")
    print("sample generation (request 0):", results[rids[0]])
    eng.pool.check_invariants()
    if tracer is not None:
        from repro.obs.trace import percentile, request_latencies

        n = tracer.export_jsonl(args.obs_spans)
        lats = request_latencies(tracer.spans)
        p50 = percentile([r["ttft"] for r in lats], 50)
        p99 = percentile([r["ttft"] for r in lats], 99)
        print(f"spans: {n} exported to {args.obs_spans}; "
              f"TTFT p50={p50} p99={p99} (s)")
    if registry is not None:
        from repro.obs.metrics import collect_process_metrics

        collect_process_metrics(registry)
        if args.obs_metrics:
            registry.export_jsonl(args.obs_metrics)
        if args.obs_prometheus:
            registry.export_prometheus(args.obs_prometheus)
    out = {"tok_per_s": float(toks_per_s), "results": results,
           "kv_ratio": f32 / packed, "max_concurrent": eng.max_concurrent,
           "preemptions": eng.preemptions, "restores": eng.restores,
           "utilization": eng.utilization(), "events": list(eng.events),
           "compile_stats": cstats, "engine": eng}
    if args.spec_decode:
        out.update(spec_rounds=eng.spec_rounds,
                   acceptance_rate=eng.acceptance_rate(),
                   spec_rollback_tokens=eng.spec_rollback_tokens)
    return out


def _legacy_main(args, cfg, model, params) -> dict:
    """Static-batch prefill + greedy decode (ssm / hybrid / encdec, whose
    recurrent or cross-attention state is not paged-KV shaped)."""
    dist = Dist()
    prompt_len = args.prompt_len
    if args.prompt_lens:
        print(f"note: legacy static batch serves {args.batch} uniform "
              f"prompts of {prompt_len} tokens; --prompt-lens "
              f"{args.prompt_lens!r} applies to the paged engine only")
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size,
                                  seq_len=prompt_len,
                                  global_batch=args.batch, seed=args.seed))
    batch = with_extras(next(data), cfg, key=jax.random.PRNGKey(1))
    max_t = prompt_len + args.gen

    t0 = time.time()
    if cfg.family == "encdec":
        enc_out = encdec.encode(params, batch["frames"], cfg, dist, remat=False)
        state = encdec.init_decode_state(cfg, args.batch, max_t,
                                         enc_out.shape[1])
        state = encdec.prime_cross_attention(params, enc_out, cfg, state)
        prompt = batch["tokens"]
        step = jax.jit(lambda p, t, s, pos: encdec.decode_step(
            p, t, s, pos, cfg, dist))
        # teacher-force the prompt through the decode path, then free-run
        tok = prompt[:, :1]
        pos = 0
        for pos in range(prompt.shape[1]):
            logits, state = step(params, prompt[:, pos:pos + 1], state,
                                 jnp.int32(pos))
        tok = jnp.argmax(logits[:, 0], axis=-1)[:, None]
    else:
        last_logits = model.prefill(params, batch, cfg, dist)
        state = model.init_decode_state(cfg, args.batch, max_t)
        # replay the prompt through decode to warm the caches
        step = jax.jit(lambda p, t, s, pos: model.decode_step(
            p, t, s, pos, cfg, dist))
        prompt = batch["tokens"]
        for pos in range(prompt.shape[1]):
            logits, state = step(params, prompt[:, pos:pos + 1], state,
                                 jnp.int32(pos))
        tok = jnp.argmax(logits[:, 0], axis=-1)[:, None]
        del last_logits
    t_prefill = time.time() - t0

    out_tokens = [tok]
    t0 = time.time()
    base = prompt.shape[1]
    for i in range(args.gen - 1):
        logits, state = step(params, tok, state, jnp.int32(base + i))
        tok = jnp.argmax(logits[:, 0], axis=-1)[:, None]
        out_tokens.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.time() - t0

    gen = jnp.concatenate(out_tokens, axis=1)
    toks_per_s = args.batch * (args.gen - 1) / max(t_decode, 1e-9)
    print(f"arch={cfg.name} batch={args.batch} prompt={prompt_len} "
          f"gen={args.gen} [legacy static batch]")
    print(f"prefill: {t_prefill:.2f}s   decode: {t_decode:.2f}s "
          f"({toks_per_s:.1f} tok/s)")
    print("sample generation (seq 0):", gen[0].tolist())
    return {"tok_per_s": float(toks_per_s), "gen": gen}


if __name__ == "__main__":
    main()
