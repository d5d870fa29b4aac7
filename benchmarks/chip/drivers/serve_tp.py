"""Tensor-parallel serving driver: the program's ``ServeEngine`` with its
``ShardedModelExecutor`` over a 1-D ``model`` mesh of the cell's chips
(``program.shards``), fed by a closed loop of clients.

It is the one-chip serving driver (``serve.py``, whose ``Instruments``,
``percentile``, ``check_sample``, ``warm_rows`` and ``PARTS_S`` it
reuses) but where one chip cannot serve: the weights are made from the
seed already sharded over the mesh as the executor splits them (heads and
output columns 4 ways, the embedding replicated), the engine gets the
sharded executor with the exact ``gather`` logit wire, the configuration
maps to the program's ``ModelConfig`` by the Qwen3 keys (QKV bias from
``attention_bias``, qk-norm from the program's registry entry, the head
untied as the file says), and the plain reference (``reference_qwen3.py``)
runs over the same chips, its weights placed by sharding annotations
alone.  The reference scores requests still live at the window's end
too, by the tokens they served (``scored``), so the longest contexts are
read.  ``reference_gaps`` keeps the one-chip driver's meaning, so
``calibrate.py`` runs a cell of this driver unchanged.
"""

from __future__ import annotations

import dataclasses
import os
import time
from functools import partial

import harness

_ONE = harness.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "serve.py"), "driver_serve_for_tp")
Instruments, percentile = _ONE.Instruments, _ONE.percentile
check_sample, warm_rows, PARTS_S = (_ONE.check_sample, _ONE.warm_rows,
                                    _ONE.PARTS_S)
PAD_TOKENS, PAD_ROWS = _ONE.PAD_TOKENS, _ONE.PAD_ROWS


def model_config(cfg: dict, *, seq_len: int, global_batch: int):
    """The file's published keys over the program's registry entry
    (``program.arch``), which gives the family and qk-norm."""
    from repro.configs import get_config
    from repro.core.policy import AccumulationPolicy, plan_for_model

    prog = cfg["program"]
    base = get_config(prog["arch"])
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    mc = dataclasses.replace(
        base, n_layers=cfg["num_hidden_layers"], d_model=d, n_heads=h,
        n_kv_heads=cfg["num_key_value_heads"],
        d_head=cfg.get("head_dim") or d // h,
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        attn_bias=bool(cfg.get("attention_bias")))
    policy = AccumulationPolicy(mode=prog["policy"], chunk=prog["chunk"],
                                rounding=prog.get("rounding", "rne"))
    return plan_for_model(mc, seq_len=seq_len, global_batch=global_batch,
                          policy=policy)


def mesh_of(cfg: dict):
    from repro.launch.mesh import make_serve_mesh

    return make_serve_mesh(cfg["program"]["shards"])


def shardings(cfg: dict, mesh, key) -> dict:
    """Where each of the harness's weights lives, in the program's tree:
    the executor's own split (``serve_param_specs``)."""
    import jax
    import jax.numpy as jnp

    import reference_qwen3 as RQ
    from repro.sharding.specs import named_shardings, serve_param_specs

    shapes = jax.eval_shape(lambda k: RQ.to_program(RQ.make_weights(
        cfg, k, jnp.bfloat16)), key)
    return named_shardings(serve_param_specs(
        shapes, n_shards=mesh.shape["model"], logit_wire="gather"), mesh)


def run(cell, *, seed, seconds, trace_dir, process_start, log):
    import jax
    import jax.numpy as jnp

    import reference_qwen3 as RQ
    import traffic as T
    from harness import CompileWatch, memory_peak
    from programcfg import check_tree

    from repro.models.api import get_model
    from repro.obs.trace import Tracer
    from repro.quant.formats import FPFormat
    from repro.serve.kvcache import PagedKVConfig
    from repro.serve.plan import plan_attention
    from repro.serve.scheduler import ServeEngine, ShardedModelExecutor

    cfg, mix, prog = cell.config, cell.mix, cell.config["program"]
    if mix["loop"] != "closed":
        raise ValueError(f"{cell.traffic_name}: the tensor-parallel driver "
                         "runs closed loops only")
    vocab = cfg["vocab_size"]
    page, n_pages, chunk = prog["page_size"], prog["n_pages"], \
        prog["prefill_chunk"]
    max_ctx = mix["prompt"]["max"] + mix["output"]["max"]
    model = get_model(model_config(cfg, seq_len=max_ctx,
                                   global_batch=prog["max_batch"]))
    mesh = mesh_of(cfg)
    wkey = jax.random.PRNGKey(T.jax_seed(seed, 0))
    make = jax.jit(lambda k: RQ.to_program(RQ.make_weights(
        cfg, k, jnp.bfloat16)), out_shardings=shardings(cfg, mesh, wkey))
    check_tree(jax.eval_shape(model.init_params, wkey),
               jax.eval_shape(make, wkey))
    params = make(wkey)
    full = plan_attention((n_pages - 1) * page, page,
                          prefill_chunk_tokens=chunk,
                          tp_shards=prog["shards"])
    reach = next(i for i, bk in enumerate(full.buckets)
                 if bk.max_ctx >= max_ctx)
    plan = dataclasses.replace(full, buckets=full.buckets[:reach + 1])
    kv_fmt = FPFormat(e=prog["kv_fmt"][0], m=prog["kv_fmt"][1])
    pc = PagedKVConfig.for_model(model.cfg, n_pages=n_pages, page_size=page,
                                 kv_fmt=kv_fmt)
    ex = ShardedModelExecutor(model, params, pc, kv_fmt=kv_fmt, mesh=mesh,
                              max_batch=prog["max_batch"],
                              logit_wire="gather")
    tracer = Tracer() if trace_dir is not None else None
    eng = ServeEngine(model, params, n_pages=n_pages, page_size=page,
                      kv_fmt=kv_fmt, plan=plan, max_batch=prog["max_batch"],
                      prefill_chunk_tokens=chunk, executor=ex,
                      tracer=tracer)
    warm = eng.warmup()
    t = time.perf_counter()
    rows = warm_rows(eng)
    log(f"mesh {dict(mesh.shape)}; warm-up: {warm} for buckets "
        f"{[bk.max_ctx for bk in plan.buckets]}; decode at 1..{rows} rows "
        f"in {time.perf_counter() - t:.2f} s")
    inst = Instruments(eng) if trace_dir is not None else None

    reqs = T.Requests(mix, vocab, seed, block=mix["clients"])
    emitted: dict[int, list[float]] = {}   # rid -> emission time per token
    seen: dict[int, int] = {}
    prompts: dict[int, list[int]] = {}
    due: dict[int, float] = {}
    n_fin = 0

    def submit(prompt, out, t_due):
        rid = eng.submit(prompt, out)
        prompts[rid], due[rid], seen[rid] = prompt, t_due, 0
        emitted[rid] = []
        return rid

    def tick():
        """One engine tick; returns the rids that finished in it."""
        nonlocal n_fin
        eng.step()
        now = time.perf_counter()
        for rid, seq in eng.active.items():
            k = len(seq.generated)
            if k > seen[rid]:
                emitted[rid].extend([now] * (k - seen[rid]))
                seen[rid] = k
        done = list(eng.finished)[n_fin:]
        n_fin = len(eng.finished)
        for rid in done:
            k = len(eng.finished[rid])
            emitted[rid].extend([now] * (k - seen[rid]))
            seen[rid] = k
        return done

    def refill(done):
        nonlocal next_i
        for rid in done:
            owner[submit(*reqs.get(next_i),
                         time.perf_counter())] = owner.pop(rid)
            next_i += 1

    # ------------------------------ warm phase ----------------------------
    owner = {}
    t = time.perf_counter()
    for c, (prompt, out) in enumerate(T.first_wave(mix, vocab, seed)):
        owner[submit(prompt, out, t)] = c
    next_i = mix["clients"]
    wave = set(owner)
    while any(not emitted[r] for r in wave):
        refill(tick())

    # ------------------------------- window -------------------------------
    watch = CompileWatch()
    t0 = time.perf_counter()
    setup_s = t0 - process_start
    watch.on = True
    tracing = False
    if trace_dir is not None:
        jax.profiler.start_trace(trace_dir)
        tracing = inst.record = True
    while True:
        now = time.perf_counter()
        if now - t0 >= seconds:
            break
        if tracing and now - t0 >= _ONE.TRACE_S:
            jax.profiler.stop_trace()
            tracing = False
            inst.record = False
        refill(tick())
    t_end = time.perf_counter()
    compiled = watch.close()
    if tracing:
        jax.profiler.stop_trace()
        inst.record = False
    window = t_end - t0

    in_window = [t for ts in emitted.values() for t in ts if t0 <= t < t_end]
    gaps = [(b - a) * 1e3 for ts in emitted.values()
            for a, b in zip(ts, ts[1:]) if t0 <= b < t_end]
    metrics = {"serve_tokens_per_s": len(in_window) / window,
               "itl_p95_ms": percentile(gaps, 95), "setup_s": setup_s}
    attempted = sum(1 for r in due if due[r] < t_end)
    log(f"window {window:.3f} s: {len(in_window)} tokens, "
        f"{len(gaps)} gaps, {eng.steps} ticks in all, metrics {metrics}")
    for part in PARTS_S:
        if part < window:
            n_tok = sum(1 for ts in emitted.values() for t in ts
                        if t0 <= t < t0 + part)
            g = [(b - a) * 1e3 for ts in emitted.values()
                 for a, b in zip(ts, ts[1:]) if t0 <= b < t0 + part]
            log(f"first {part} s of the window: {n_tok / part:.4f} "
                f"tokens/s, itl_p95_ms {percentile(g, 95):.4f}")

    counters = {}
    if inst is not None:
        from trace_reduce import flatten

        counters = {"ticks": inst.ticks, "decodes": inst.decodes,
                    "prefills": inst.prefills, "max_batch":
                    prog["max_batch"], "page_size": page,
                    "shards": prog["shards"], "window_s": window,
                    "serve_tokens_per_s": metrics["serve_tokens_per_s"]}
        flat = flatten(trace_dir)
    else:
        flat = None

    mem = memory_peak(cell.chips)
    finished = {r: list(toks) for r, toks in eng.finished.items()}
    live = {r: list(seq.generated) for r, seq in eng.active.items()}
    # free the program's weights and arena before the reference runs
    del eng, ex, params, inst

    # ------------------------------ reference -----------------------------
    served, sample = scored(prompts, finished, live, mix["check_requests"],
                            seed)
    ref = reference_gaps(cfg, wkey, prompts, served, sample)
    log(f"reference: {ref['served']} served tokens of {len(sample)} "
        f"requests {sample} (live at the window's end: "
        f"{[r for r in sample if r in live]}; contexts "
        f"{[len(prompts[r]) + len(served[r]) for r in sample]}), widest gap "
        f"{ref['worst']}, mean gap {ref['mean']}")
    return {
        "complete": ref["served"] > 0,
        "attempted": attempted, "failed": 0, "memory_peak_bytes": mem,
        "metrics": metrics,
        "checks": [("mean_gap", ref["mean"]), ("token_gap", ref["worst"])],
        "window_compiles": compiled, "counters": counters, "trace": flat,
        "reference": ref,
    }


def scored(prompts: dict, finished: dict, live: dict, n: int,
           seed: int) -> tuple[dict, list[int]]:
    """What the reference scores: the tokens each request served, finished
    or still live when the window closed, and ``check_sample``'s pick of
    them by context (prompt and served tokens), so the request with the
    longest context and ``n - 1`` more drawn from the seed.  An answer of
    this mix outlives a window, so a sample of finished requests alone
    would hold only the first wave's short tail."""
    served = {r: t for r, t in live.items() if t}
    served.update(finished)
    return served, check_sample({r: prompts[r] + t
                                 for r, t in served.items()}, n, seed)


def reference_gaps(cfg, wkey, prompts, finished, sample, lows=()):
    """As the one-chip driver's: at every served token of the sample, the
    gap by which the reference's logit of it lies below its best, widest
    (``worst``) and mean; for each precision in ``lows``, the same two
    numbers for the reference at that precision read at its own top token.

    The reference's weights are made from the seed straight onto the
    cell's mesh as the program's were (bf16, sharding annotations only).
    Every sequence of the sample is padded at the end to the longest one's
    multiple of ``PAD_TOKENS`` (causal attention: the padding changes no
    earlier position), and its scored rows to a multiple of ``PAD_ROWS``,
    so the reference compiles once for each precision."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import reference_qwen3 as RQ

    mesh = mesh_of(cfg)
    where = RQ.from_program(shardings(cfg, mesh, wkey))
    w = jax.jit(partial(RQ.make_weights, cfg, dtype=jnp.bfloat16),
                out_shardings=where)(wkey)
    fns = {p: jax.jit(lambda w, t, r, p=p: RQ.logits(cfg, w, t, p, r))
           for p in ("f32", *lows)}
    gaps = {p: [] for p in ("f32", *lows)}
    seqs = {rid: prompts[rid] + finished[rid][:-1] for rid in sample}
    n_tok = -(-max((len(s) for s in seqs.values()), default=1)
              // PAD_TOKENS) * PAD_TOKENS
    n_row = -(-max((len(finished[r]) for r in sample), default=1)
              // PAD_ROWS) * PAD_ROWS
    for rid in sample:
        prompt, gen, seq = prompts[rid], finished[rid], seqs[rid]
        toks = np.zeros(n_tok, np.int32)
        toks[:len(seq)] = seq
        rows = np.zeros(n_row, np.int32)
        rows[:len(gen)] = np.arange(len(prompt) - 1, len(seq))
        lg = np.asarray(fns["f32"](w, toks, rows))[:len(gen)]
        best = lg.max(-1)
        at = np.arange(len(gen))
        gaps["f32"].append(best - lg[at, np.asarray(gen)])
        for p in lows:
            top = np.asarray(fns[p](w, toks, rows))[:len(gen)].argmax(-1)
            gaps[p].append(best - lg[at, top])

    def summary(parts):
        g = np.concatenate(parts) if parts else np.zeros(0)
        return {"worst": float(g.max()) if g.size else 0.0,
                "mean": float(g.mean()) if g.size else 0.0}

    return {**summary(gaps["f32"]),
            "served": sum(len(finished[r]) for r in sample),
            "low": {p: summary(gaps[p]) for p in lows}}
