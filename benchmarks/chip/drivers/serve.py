"""Serving driver: the program's ``ServeEngine`` over its paged KV arena,
fed by a closed loop of clients or by open-loop arrivals.

Set-up makes bf16 weights on the device from the seed, builds the engine
with the plan's buckets up to the longest context the mix can reach, and
compiles exactly those (``ServeEngine.warmup``) and the decode call's
eager tail at every row count (``warm_rows``).  A warm phase runs the mix
until it is in steady state; the window then runs ``seconds`` of engine
ticks, with no program compiled or loaded inside it.  Each token's emission time is taken on the host clock when
the tick that produced it returns.  After the window the plain reference
scores a seeded sample of the finished requests, the longest among them.
"""

from __future__ import annotations

import dataclasses
import math
import time
from functools import partial

TRACE_S = 3.0        # seconds of the window the profiler records
GRACE_S = 60.0       # an open-loop request due in the window may finish late


def percentile(values, q: float) -> float:
    """Nearest-rank percentile; +inf stands for a request never served."""
    xs = sorted(values)
    if not xs:
        return float("nan")
    k = max(math.ceil(q / 100.0 * len(xs)) - 1, 0)
    return float(xs[k])


class Instruments:
    """Host-clock wrappers around the calls into each layer (traced runs
    only): the engine's tick, the executor's prefill and decode.  Each
    call is also a ``TraceAnnotation`` so the profiler puts the host's
    doing beside the device's."""

    def __init__(self, eng):
        import jax

        self.eng, self.ex = eng, eng.executor
        self.ticks = []          # (tick ms, ms inside executor calls)
        self.decodes = []        # (ms, live rows, [context per row])
        self.prefills = []       # (ms, q_offset, q_len, slab width, final)
        self.record = False  # only while the profiler records
        self._in_exec = 0.0
        ann = jax.profiler.TraceAnnotation
        ex_prefill, ex_decode, step = (self.ex.prefill, self.ex.decode,
                                       eng.step)

        def prefill(req):
            t = time.perf_counter()
            with ann("bench.serve.prefill"):
                out = ex_prefill(req)
            dt = (time.perf_counter() - t) * 1e3
            self._in_exec += dt
            if self.record:
                self.prefills.append((dt, req.t0, len(req.tokens),
                                      req.slab_width or len(req.tokens),
                                      bool(req.final)))
            return out

        def decode(req):
            t = time.perf_counter()
            with ann("bench.serve.decode"):
                out = ex_decode(req)
            dt = (time.perf_counter() - t) * 1e3
            self._in_exec += dt
            if self.record:
                self.decodes.append((dt, len(req.last_tokens),
                                     list(req.seq_lens)))
            return out

        def tick():
            self._in_exec = 0.0
            t = time.perf_counter()
            with ann("bench.serve.step"):
                out = step()
            if self.record:
                self.ticks.append(((time.perf_counter() - t) * 1e3,
                                   self._in_exec))
            return out

        self.ex.prefill, self.ex.decode, eng.step = prefill, decode, tick


def run(cell, *, seed, seconds, trace_dir, process_start, log):
    import jax
    import jax.numpy as jnp

    import reference as R
    import traffic as T
    from harness import CompileWatch, memory_peak
    from programcfg import check_tree, model_config

    from repro.models.api import get_model
    from repro.obs.trace import Tracer
    from repro.quant.formats import FPFormat
    from repro.serve.plan import plan_attention
    from repro.serve.scheduler import ServeEngine

    cfg, mix, prog = cell.config, cell.mix, cell.config["program"]
    vocab = cfg["vocab_size"]
    page, n_pages, chunk = prog["page_size"], prog["n_pages"], \
        prog["prefill_chunk"]
    max_ctx = mix["prompt"]["max"] + mix["output"]["max"]
    model = get_model(model_config(cfg, seq_len=max_ctx,
                                   global_batch=prog["max_batch"]))
    wkey = jax.random.PRNGKey(T.jax_seed(seed, 0))
    make = jax.jit(lambda k: R.to_program(R.make_weights(
        cfg, k, jnp.bfloat16)))
    check_tree(jax.eval_shape(model.init_params, wkey),
               jax.eval_shape(make, wkey))
    params = make(wkey)
    full = plan_attention((n_pages - 1) * page, page,
                          prefill_chunk_tokens=chunk)
    reach = next(i for i, bk in enumerate(full.buckets)
                 if bk.max_ctx >= max_ctx)
    plan = dataclasses.replace(full, buckets=full.buckets[:reach + 1])
    tracer = Tracer() if trace_dir is not None else None
    eng = ServeEngine(model, params, n_pages=n_pages, page_size=page,
                      kv_fmt=FPFormat(e=prog["kv_fmt"][0],
                                      m=prog["kv_fmt"][1]),
                      plan=plan, max_batch=prog["max_batch"],
                      prefill_chunk_tokens=chunk, tracer=tracer)
    warm = eng.warmup()
    t = time.perf_counter()
    rows = warm_rows(eng)
    log(f"warm-up: {warm} for buckets "
        f"{[bk.max_ctx for bk in plan.buckets]}; decode at 1..{rows} rows "
        f"in {time.perf_counter() - t:.2f} s")
    inst = Instruments(eng) if trace_dir is not None else None

    closed = mix["loop"] == "closed"
    # a closed loop's replacements come in blocks of ``clients``
    reqs = T.Requests(mix, vocab, seed,
                      block=mix["clients"] if closed else mix.get("block",
                                                                 256))
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

    # ------------------------------ warm phase ----------------------------
    next_i = 0
    if closed:
        owner = {}
        t = time.perf_counter()
        for c, (prompt, out) in enumerate(T.first_wave(mix, vocab, seed)):
            owner[submit(prompt, out, t)] = c
        next_i = mix["clients"]
        wave = set(owner)
        while any(not emitted[r] for r in wave):
            for rid in tick():
                owner[submit(*reqs.get(next_i),
                             time.perf_counter())] = owner.pop(rid)
                next_i += 1
        schedule = None
    else:
        warm_s = mix["warm_s"]
        n_due = int(mix["rate_per_s"] * (warm_s + seconds + GRACE_S)) + 16
        schedule = T.arrival_times(mix, n_due, seed)
        t_base = time.perf_counter()
        late = []

        def arrivals(now):
            nonlocal next_i
            while next_i < n_due and t_base + schedule[next_i] <= now:
                submit(*reqs.get(next_i), t_base + schedule[next_i])
                late.append(now - (t_base + schedule[next_i]))
                next_i += 1

        while time.perf_counter() < t_base + warm_s:
            arrivals(time.perf_counter())
            if eng.pending or eng.active or eng.swapped:
                tick()
            else:
                time.sleep(0.0005)

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
        if tracing and now - t0 >= TRACE_S:
            jax.profiler.stop_trace()
            tracing = False
            inst.record = False
        if closed:
            for rid in tick():
                owner[submit(*reqs.get(next_i),
                             time.perf_counter())] = owner.pop(rid)
                next_i += 1
        else:
            arrivals(now)
            if eng.pending or eng.active or eng.swapped:
                tick()
            else:
                time.sleep(0.0005)
    t_end = time.perf_counter()
    compiled = watch.close()
    if tracing:
        jax.profiler.stop_trace()
        inst.record = False
    window = t_end - t0

    # end-to-end numbers
    in_window = [t for ts in emitted.values() for t in ts if t0 <= t < t_end]
    gaps = [(b - a) * 1e3 for ts in emitted.values()
            for a, b in zip(ts, ts[1:]) if t0 <= b < t_end]
    metrics = {"serve_tokens_per_s": len(in_window) / window,
               "itl_p95_ms": percentile(gaps, 95), "setup_s": setup_s}
    attempted = sum(1 for r in due if due[r] < t_end)
    failed = 0
    if not closed:
        window_rids = [r for r in due if t0 <= due[r] < t_end]
        backlog = sum(1 for r in due if due[r] < t_end and not emitted[r])
        deadline = t_end + GRACE_S
        while any(not emitted[r] for r in window_rids) and \
                time.perf_counter() < deadline:
            arrivals(time.perf_counter())
            tick()
        ttft = [((emitted[r][0] - due[r]) * 1e3) if emitted[r]
                else float("inf") for r in window_rids]
        failed = sum(1 for r in window_rids if not emitted[r])
        attempted = len(window_rids)
        metrics["ttft_p90_ms"] = percentile(ttft, 90)
        log(f"generator lateness: max {max(late) * 1e3:.3f} ms, mean "
            f"{sum(late) / len(late) * 1e3:.3f} ms over {len(late)} "
            f"arrivals; {len(window_rids)} requests due in the window, "
            f"{backlog} due and not yet served a first token at its end")
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
                    "window_s": window, "serve_tokens_per_s":
                    metrics["serve_tokens_per_s"],
                    "queued_ms": [s.duration * 1e3 for s in tracer.spans
                                  if s.name == "queued"
                                  and s.t_end is not None
                                  and t0 <= s.t_start < t_end]}
        flat = flatten(trace_dir)
    else:
        flat = None

    mem = memory_peak(cell.chips)
    finished = {r: list(toks) for r, toks in eng.finished.items()}
    # free the program's weights and arena before the reference runs
    del eng, params, inst

    # ------------------------------ reference -----------------------------
    sample = check_sample(finished, mix["check_requests"], seed)
    ref = reference_gaps(cfg, wkey, prompts, finished, sample)
    log(f"reference: {ref['served']} served tokens of {len(sample)} "
        f"requests {sample}, widest gap {ref['worst']}, mean gap "
        f"{ref['mean']}")
    return {
        "complete": failed == 0 and ref["served"] > 0,
        "attempted": attempted, "failed": failed, "memory_peak_bytes": mem,
        "metrics": metrics,
        "checks": [("mean_gap", ref["mean"]), ("token_gap", ref["worst"])],
        "window_compiles": compiled, "counters": counters, "trace": flat,
        "reference": ref,
    }


def check_sample(finished: dict, n: int, seed: int) -> list[int]:
    """The longest finished request and ``n - 1`` more drawn from the seed."""
    import traffic as T

    rids = sorted(finished)
    if not rids:
        return []
    longest = max(rids, key=lambda r: (len(finished[r]), -r))
    rest = [r for r in rids if r != longest]
    pick = T.rng(seed, 5).permutation(len(rest))[:max(n - 1, 0)]
    return [longest] + sorted(rest[i] for i in pick)


def warm_rows(eng) -> int:
    """``ModelExecutor.decode`` slices and argmaxes the step's logits
    eagerly at the live-row count, a program for each count.  Call it once
    at every count the engine can reach, on rows like the executor's own
    padding (the null page, length 0, which change no sequence's pages),
    so that none of those programs compiles inside the window.  Returns
    the largest count."""
    from repro.models.api import DecodeRequest

    bk = eng.plan.buckets[0]
    width = bk.max_pages(eng.page_size)
    for n in range(1, eng.max_batch + 1):
        eng.executor.decode(DecodeRequest(
            rids=tuple(range(n)), last_tokens=(0,) * n,
            page_table=((0,) * width,) * n, positions=(0,) * n,
            seq_lens=(0,) * n, acc=bk.acc))
    return eng.max_batch


def reference_gaps(cfg, wkey, prompts, finished, sample, lows=()):
    """The reference's reading of every served token of the sample: the
    gap by which the reference's logit of the served token lies below its
    best, widest (``worst``) and mean.  For each precision in ``lows``
    (a name ``reference.rounded`` takes) the same two numbers for the control: the
    reference at that precision, read at its own top token.

    Sequences are padded at the end to a multiple of ``PAD_TOKENS``
    (causal attention: the padding changes no earlier position) and the
    scored rows to a multiple of ``PAD_ROWS``, so the reference compiles
    for a few shapes only."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import reference as R

    w = jax.jit(partial(R.make_weights, cfg, dtype=jnp.bfloat16))(wkey)
    fns = {p: jax.jit(lambda w, t, r, p=p: R.logits(cfg, w, t, p, r))
           for p in ("f32", *lows)}
    gaps = {p: [] for p in ("f32", *lows)}
    for rid in sample:
        prompt, gen = prompts[rid], finished[rid]
        seq = prompt + gen[:-1]
        n_tok = -(-len(seq) // PAD_TOKENS) * PAD_TOKENS
        n_row = -(-len(gen) // PAD_ROWS) * PAD_ROWS
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


PAD_TOKENS = 512
PAD_ROWS = 256
PARTS_S = (10, 20, 30, 40)   # leading parts of the window, logged
