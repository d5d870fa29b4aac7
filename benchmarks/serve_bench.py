"""Serving benchmark: continuous batching over the paged QTensor KV-cache.

Interpret-mode wall-times are a correctness proxy (see kernel_bench.py);
the quantities that transfer are the pallas-pass accounting (one
HBM round-trip per pallas_call: the decode step must cost exactly ONE
attention pass per layer, with no standalone quantize/pack/unpack passes),
the KV-cache bytes-per-token compression vs the f32 carrier, the
logit-exactness of the kernel path against the unfused f32-KV oracle, and
the continuous-batching demo itself (>= 3 concurrently admitted sequences
of different lengths through one arena).

The scheduler side is measured by the **bursty-arrival utilization
scenario**: the same seeded virtual-clock traces (``repro.serve.sim``)
replayed against the chunked-prefill + optimistic-admission + preemption
engine and against the one-prefill-per-step worst-case-reservation
baseline.  CI gates utilization (decoded tokens per decode-batch slot) of
the new scheduler >= the baseline, that the traces actually forced
preemptions/swaps, and that the scheduler change left KV bytes/token
untouched.

The compile tax is measured by the **cold-vs-warm scenario** (which runs
FIRST — the serve compile cache is process-wide, so any engine driven
earlier would pre-warm the "cold" side): a cold engine pays its traces
inline on the way to its first tokens; a warm-started engine with the
same configuration then serves different ragged prompts.  CI gates the
warm engine's steady-state compile count at exactly ZERO — every slab of
every prompt must land on a bucket's already-compiled kernel.

The **speculative-decoding scenario** measures what speculation buys in
the unit that transfers — tokens committed per TARGET decode pass — on a
deterministic sim comparison (spec streams bitwise the plain engine's),
then drives the REAL smoke draft/target pair through a warm-started
``SpecDecodeEngine``: CI gates the sim speedup >= 1.5x, zero steady-state
compiles with spec on, and KV bytes/token untouched by the spec lane.

Writes ``BENCH_serve.json``; CI gates on the compression ratio, the pass
count, logit exactness, the concurrency of the demo run, the bursty
utilization comparison, the zero-steady-state-compile property and the
speculative-decoding scenario.
"""

from __future__ import annotations

import functools
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke_config
from repro.kernels.common import count_pallas_executions
from repro.models import lm
from repro.models.api import get_model
from repro.obs import Tracer, percentile, request_latencies
from repro.serve.scheduler import ServeEngine
from repro.serve.sim import SimExecutor, bursty_utilization_comparison
from repro.serve.spec import SpecDecodeEngine

PAGE_SIZE = 8
N_PAGES = 40
PROMPT_LENS = (6, 13, 21)
GEN = 8
PREFILL_CHUNK = PAGE_SIZE  # demo engine runs chunked prefill

# realized KV bytes/token of the pre-chunking engine at THIS bench config
# (2-layer smoke, page 8): K+V int8 payloads + amortized per-page scale
# exponents.  The scheduler PR must not move it.
KV_BYTES_PER_TOKEN_BASELINE = 130.0


def _passes_per_decode_step(model, params, eng) -> int:
    """Per-execution pallas_call count of one batched decode step (the
    rolled layer scan is weighted by its trip count)."""
    b = len(PROMPT_LENS)
    _, bucket = eng.plan.bucket_for(max(PROMPT_LENS) + GEN)
    width = bucket.max_pages(PAGE_SIZE)
    fn = functools.partial(lm.paged_decode, cfg=model.cfg,
                          kv_fmt=eng.kv_fmt, acc=bucket.acc)
    return count_pallas_executions(
        fn, params, jnp.zeros((b, 1), jnp.int32), eng.kv,
        jnp.zeros((b, width), jnp.int32),
        jnp.asarray([p - 1 for p in PROMPT_LENS], jnp.int32),
        jnp.asarray(PROMPT_LENS, jnp.int32))


def _logit_exact(model, params, eng) -> bool:
    """Kernel decode vs the unfused f32-KV oracle, on a live mixed-length
    state (the acceptance gate's logit-exactness check)."""
    rng = np.random.RandomState(0)
    kv_state = lm.init_paged_state(model.cfg, n_pages=16, page_size=PAGE_SIZE)
    _, bucket = eng.plan.bucket_for(max(PROMPT_LENS))
    pages = {0: [1, 2], 1: [3]}
    lens = {0: 11, 1: 5}
    for i, pg in pages.items():
        toks = jnp.asarray([rng.randint(0, model.cfg.vocab_size, lens[i])],
                           jnp.int32)
        pg_ids = jnp.asarray(pg, jnp.int32)
        _, kv_state = lm.paged_prefill(params, toks, kv_state, pg_ids, pg_ids,
                                       0, toks.shape[1], model.cfg,
                                       kv_fmt=eng.kv_fmt, acc=bucket.acc)
    pt = jnp.asarray([[1, 2], [3, 0]], jnp.int32)
    positions = jnp.asarray([lens[0], lens[1]], jnp.int32)
    tokens = jnp.asarray([[7], [9]], jnp.int32)
    kw = dict(cfg=model.cfg, kv_fmt=eng.kv_fmt, acc=bucket.acc)
    lk, _ = lm.paged_decode(params, tokens, kv_state, pt, positions,
                            positions + 1, **kw)
    lo, _ = lm.paged_decode(params, tokens, kv_state, pt, positions,
                            positions + 1, oracle=True, **kw)
    return bool(np.array_equal(np.asarray(lk), np.asarray(lo)))


def _cold_vs_warm(model, params) -> dict:
    """Compile-tax scenario (see module docstring).  Per-request first-token
    latency (TTFT) is read off the request-lifecycle span tree: the tracer
    stamps the root span at submit and a token event at each emission, so
    TTFT is per-request from ITS OWN submit instant, not a shared t0.
    Latency numbers are interpret-mode wall-times (directional only) — the
    TRANSFERABLE quantity is the compile count, which is why CI gates
    ``warm_steady_compiles == 0`` and not the latencies."""
    kw = dict(n_pages=N_PAGES, page_size=PAGE_SIZE, max_batch=4,
              prefill_chunk_tokens=PREFILL_CHUNK)

    def drive(eng, tracer, prompts):
        rids = [eng.submit(p, GEN) for p in prompts]
        eng.run()
        lat = request_latencies(tracer.to_dicts())
        assert {r["rid"] for r in lat} == set(rids)
        return [r["ttft"] for r in lat]

    rng = np.random.RandomState(2)
    cfg = model.cfg
    cold_prompts = [list(rng.randint(0, cfg.vocab_size, n))
                    for n in PROMPT_LENS]
    # the warm engine serves DIFFERENT ragged prompt geometries — zero
    # steady-state compiles must hold per bucket, not per exact shape
    warm_prompts = [list(rng.randint(0, cfg.vocab_size,
                                     int(rng.randint(3, 23))))
                    for _ in range(4)]

    cold_tr = Tracer()
    cold = ServeEngine(model, params, tracer=cold_tr, **kw)
    c0 = cold.compile_stats()
    cold_lat = drive(cold, cold_tr, cold_prompts)
    c1 = cold.compile_stats()

    warm_tr = Tracer()
    warm = ServeEngine(model, params, tracer=warm_tr, **kw)
    w0 = warm.compile_stats()
    warm.warmup()
    w1 = warm.compile_stats()
    warm_lat = drive(warm, warm_tr, warm_prompts)
    w2 = warm.compile_stats()

    return {
        "cold_compiles": c1["compiles"] - c0["compiles"],
        "cold_first_token_p99_s": round(percentile(cold_lat, 99), 4),
        "warm_warmup_compiles": w1["compiles"] - w0["compiles"],
        "warm_steady_compiles": w2["compiles"] - w1["compiles"],
        "warm_first_token_p99_s": round(percentile(warm_lat, 99), 4),
        "warm_dispatch_hits": w2["hits"] - w1["hits"],
    }


# sharded scenario: must run in a subprocess — the forced-host device
# count is fixed at jax import, and this process needs its real single
# device for every other scenario.  The child is pinned to the CPU: on a
# TPU host this process holds the chip, so a child that wanted it would
# fail or hang
_SHARDED_SHARDS = 4
_SHARDED_CHILD = """
import dataclasses, json, jax, numpy as np
from repro.configs import get_smoke_config
from repro.models.api import get_model
from repro.quant.formats import FPFormat
from repro.serve.kvcache import PagedKVConfig
from repro.serve.plan import plan_attention
from repro.serve.scheduler import ModelExecutor, ServeEngine, ShardedModelExecutor

S = %(shards)d
# the smoke config's 4 q / 2 kv heads cannot split 4 ways
cfg = dataclasses.replace(get_smoke_config("qwen2-1.5b"),
                          n_heads=8, n_kv_heads=4)
model = get_model(cfg)
params = model.init_params(jax.random.PRNGKey(0))
kv_fmt = FPFormat(e=5, m=2)
N_PAGES, PAGE = 16, 4
pc = PagedKVConfig.for_model(cfg, n_pages=N_PAGES, page_size=PAGE,
                             kv_fmt=kv_fmt)
prompts = [list(np.random.RandomState(s).randint(1, cfg.vocab_size, n))
           for s, n in ((1, 5), (2, 9))]
plan = plan_attention((N_PAGES - 1) * PAGE, PAGE, prefill_chunk_tokens=PAGE,
                      tp_shards=S)

def drive(executor):
    eng = ServeEngine(model, params, n_pages=N_PAGES, page_size=PAGE,
                      max_batch=2, executor=executor, plan=plan,
                      prefill_chunk_tokens=PAGE)
    eng.warmup()
    warm = eng.compile_stats()["compiles"]
    rids = [eng.submit(p, 4) for p in prompts]
    streams = eng.run()
    out = {r: streams[r] for r in rids}
    steady = eng.compile_stats()["compiles"] - warm
    return eng, out, steady

eng1, out1, _ = drive(ModelExecutor(model, params, pc, kv_fmt=kv_fmt,
                                    max_batch=2))
engS, outS, steadyS = drive(ShardedModelExecutor(model, params, pc,
                                                 kv_fmt=kv_fmt, n_shards=S,
                                                 max_batch=2))
parity = out1 == outS and all(
    np.array_equal(np.asarray(eng1.kv[k]), np.asarray(engS.kv[k]))
    for k in ("k", "v", "k_se", "v_se"))
engS.pool.check_invariants()
print("SHARDED_JSON: " + json.dumps({
    "shards": S,
    "parity": bool(parity),
    "warm_steady_compiles_sharded": int(steadyS),
    "kv_bytes_per_token": round(engS.kv_bytes_per_token(), 1),
    "kv_bytes_per_token_per_shard": round(
        engS.kv_bytes_per_token(per_shard=True), 1),
    "utilization_single": round(eng1.utilization(), 4),
    "utilization_sharded": round(engS.utilization(), 4),
}))
"""


def _sharded_scenario() -> dict:
    """1-vs-N-shard parity + per-shard KV accounting on a forced-host
    mesh of _SHARDED_SHARDS devices (see _SHARDED_CHILD); returns the
    child's JSON record."""
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env = dict(os.environ)
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={_SHARDED_SHARDS} "
        + env.get("XLA_FLAGS", ""))
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", _SHARDED_CHILD % {"shards": _SHARDED_SHARDS}],
        capture_output=True, text=True, env=env, timeout=1200)
    if out.returncode != 0:
        raise RuntimeError(
            f"sharded scenario child failed:\n{out.stdout}\n{out.stderr}")
    for line in out.stdout.splitlines():
        if line.startswith("SHARDED_JSON: "):
            return json.loads(line[len("SHARDED_JSON: "):])
    raise RuntimeError(f"sharded scenario emitted no record:\n{out.stdout}")


SPEC_K_SIM = 3     # sim half: 3 drafts/round through the stamped arenas
SPEC_K_REAL = 2    # real half: keeps the per-bucket verify warmup cheap


def _spec_scenario(model, params) -> dict:
    """Speculative-decoding scenario, two halves.

    SIM (deterministic step counts): the same request mix through a plain
    engine and a ``SpecDecodeEngine`` whose draft-lane wrongness knob is
    tuned to a high-acceptance regime (~7/8 of positions agree).  The
    transferable throughput quantity is tokens committed per TARGET decode
    pass — wall clock in interpret mode measures the interpreter, but the
    target-pass count is exactly what speculation buys down.  CI gates the
    ratio >= 1.5x and that the spec streams are bitwise the plain ones.

    REAL smoke pair (qwen2-1.5b target / qwen2-0.5b draft): a warm-started
    spec engine serves ragged traffic; CI gates ZERO steady-state compiles
    across BOTH executors and that the target arena's KV bytes/token is
    untouched by the spec lane (the draft arena is separate HBM, never a
    layout change)."""
    # --- sim half -----------------------------------------------------
    def drive(spec: bool):
        ex = SimExecutor(n_pages=20, page_size=PAGE_SIZE, vocab_size=211)
        kw = dict(n_pages=20, page_size=PAGE_SIZE, max_batch=4, executor=ex)
        if spec:
            dn = 20 + 4 * (-(-(SPEC_K_SIM + 1) // PAGE_SIZE))
            dex = SimExecutor(
                n_pages=dn, page_size=PAGE_SIZE, vocab_size=211,
                draft_wrong=lambda rid, idx: (rid * 7919
                                              + idx * 104_729) % 8 == 0)
            eng = SpecDecodeEngine(None, None, spec_k=SPEC_K_SIM,
                                   draft_executor=dex, draft_n_pages=dn, **kw)
        else:
            eng = ServeEngine(None, None, **kw)
        rng = np.random.RandomState(5)
        rids = [eng.submit(list(rng.randint(1, 211, n)), 12)
                for n in (6, 13, 9, 4)]
        out = eng.run()
        eng.pool.check_invariants()
        return eng, [tuple(out[r]) for r in rids]

    plain_eng, plain_streams = drive(spec=False)
    spec_eng, spec_streams = drive(spec=True)
    tps_plain = plain_eng.decoded_tokens / max(plain_eng._decode_steps, 1)
    tps_spec = spec_eng.decoded_tokens / max(spec_eng._decode_steps, 1)

    # --- real half ----------------------------------------------------
    dcfg = get_smoke_config("qwen2-0.5b")
    dmodel = get_model(dcfg)
    dparams = dmodel.init_params(jax.random.PRNGKey(7))
    eng = SpecDecodeEngine(model, params, spec_k=SPEC_K_REAL,
                           draft_model=dmodel, draft_params=dparams,
                           n_pages=N_PAGES, page_size=PAGE_SIZE, max_batch=4,
                           prefill_chunk_tokens=PREFILL_CHUNK,
                           warm_start=True)
    rng = np.random.RandomState(6)
    with eng.executor.compile_stats_scope() as d_t, \
            eng.draft_executor.compile_stats_scope() as d_d:
        for n in PROMPT_LENS:
            eng.submit(list(rng.randint(0, model.cfg.vocab_size, n)), GEN)
        t0 = time.time()
        eng.run()
        dt = max(time.time() - t0, 1e-9)
    eng.pool.check_invariants()
    packed = eng.kv_bytes_per_token()

    return {
        "sim_k": SPEC_K_SIM,
        "sim_tokens_per_target_pass_plain": round(tps_plain, 3),
        "sim_tokens_per_target_pass_spec": round(tps_spec, 3),
        "sim_speedup_target_passes": round(tps_spec / tps_plain, 3),
        "sim_streams_identical": spec_streams == plain_streams,
        "sim_acceptance_rate": round(spec_eng.acceptance_rate(), 3),
        "real_k": SPEC_K_REAL,
        "real_acceptance_rate": round(eng.acceptance_rate(), 3),
        "real_spec_rounds": eng.spec_rounds,
        "real_rollback_tokens": eng.spec_rollback_tokens,
        "real_tokens_per_s": round(eng.decoded_tokens / dt, 2),
        "warm_steady_compiles_spec": d_t["compiles"] + d_d["compiles"],
        "kv_bytes_per_token_spec": round(packed, 1),
        "kv_bytes_unchanged_by_spec": abs(
            packed - KV_BYTES_PER_TOKEN_BASELINE) < 1e-6,
    }


def run(json_path: str = "BENCH_serve.json") -> dict:
    cfg = get_smoke_config("qwen2-1.5b")
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))

    # FIRST: the cold measurement is only cold while the process compile
    # cache is empty — every other engine below shares (and warms) it
    cold_vs_warm = _cold_vs_warm(model, params)

    tracer = Tracer()
    eng = ServeEngine(model, params, n_pages=N_PAGES, page_size=PAGE_SIZE,
                      max_batch=4, monitor_cadence=5, tracer=tracer,
                      prefill_chunk_tokens=PREFILL_CHUNK)
    rng = np.random.RandomState(1)
    rids = [eng.submit(list(rng.randint(0, cfg.vocab_size, n)), GEN)
            for n in PROMPT_LENS]

    t0 = time.time()
    results = eng.run()
    dt = max(time.time() - t0, 1e-9)

    # TTFT/TPOT from the span tree (wall clock; interpret-mode, so
    # directional only — the attribution MECHANISM is what transfers)
    lat = request_latencies(tracer.to_dicts())
    latency = {
        "requests": len(lat),
        "ttft_p50_s": percentile([r["ttft"] for r in lat], 50),
        "ttft_p99_s": percentile([r["ttft"] for r in lat], 99),
        "tpot_p50_s": percentile([r["tpot"] for r in lat], 50),
        "tpot_p99_s": percentile([r["tpot"] for r in lat], 99),
    }
    latency = {k: round(v, 4) if isinstance(v, float) else v
               for k, v in latency.items()}

    packed = eng.kv_bytes_per_token()
    f32 = eng.kv_bytes_per_token(carrier_bytes=4)
    bf16 = eng.kv_bytes_per_token(carrier_bytes=2)
    passes = _passes_per_decode_step(model, params, eng)
    exact = _logit_exact(model, params, eng)
    # the pinned virtual-clock comparison vs the reservation baseline —
    # scenario and aggregation shared with tests/test_serve_sim.py
    bursty = bursty_utilization_comparison()
    # the scheduler work must leave the cache geometry alone: the realized
    # bytes/token must still equal the pre-chunking (PR 4) value for this
    # exact bench config — a scheduler change that smuggled in per-sequence
    # metadata, a different scale layout or swap-time repacking would move
    # this number (swap blobs are transient HOST memory and don't count)
    kv_unchanged = abs(packed - KV_BYTES_PER_TOKEN_BASELINE) < 1e-6
    sharded = _sharded_scenario()
    spec = _spec_scenario(model, params)

    out = {
        "arch": cfg.name,
        "prompt_lens": list(PROMPT_LENS),
        "gen": GEN,
        "page_size": PAGE_SIZE,
        "n_pages": N_PAGES,
        "prefill_chunk_tokens": PREFILL_CHUNK,
        "prefill_slabs": eng.prefill_slabs,
        "preemptions_demo": eng.preemptions,
        "cold_vs_warm": cold_vs_warm,
        "bursty": bursty,
        "kv_bytes_unchanged_by_scheduler": kv_unchanged,
        "decode_tokens": eng.decoded_tokens,
        "tokens_per_s": round(eng.decoded_tokens / dt, 2),
        "max_concurrent": eng.max_concurrent,
        "pallas_passes_per_decode_step": passes,
        "attention_layers": cfg.n_layers,
        "pallas_passes_per_decoded_token": round(
            passes / len(PROMPT_LENS), 3),
        "kv_bytes_per_token_packed": round(packed, 1),
        "kv_bytes_per_token_f32": round(f32, 1),
        "kv_bytes_per_token_bf16": round(bf16, 1),
        "kv_compression_vs_f32": round(f32 / packed, 3),
        "kv_compression_vs_bf16": round(bf16 / packed, 3),
        "logit_exact_vs_f32_oracle": exact,
        "latency_from_spans": latency,
        "sharded": sharded,
        "spec": spec,
        "monitor_events": list(eng.events),
        "generated": {int(r): results[r] for r in rids},
    }
    eng.pool.check_invariants()

    print("### serve bench (interpret mode on CPU — correctness proxy)")
    for k in ("tokens_per_s", "max_concurrent",
              "pallas_passes_per_decode_step",
              "pallas_passes_per_decoded_token",
              "kv_bytes_per_token_packed", "kv_bytes_per_token_f32",
              "kv_compression_vs_f32", "kv_compression_vs_bf16",
              "logit_exact_vs_f32_oracle", "prefill_slabs",
              "kv_bytes_unchanged_by_scheduler"):
        print(f"  {k:34s} {out[k]}")
    print("### cold-vs-warm compile tax (warm steady-state must be 0)")
    for k, v in cold_vs_warm.items():
        print(f"  {k:34s} {v}")
    print("### request latency from span tree (TTFT/TPOT, wall clock)")
    for k, v in latency.items():
        print(f"  {k:34s} {v}")
    print("### bursty-arrival scheduler comparison (virtual clock)")
    for k, v in bursty.items():
        print(f"  {k:34s} {v}")
    print(f"### sharded serving (1 vs {sharded['shards']} shards, "
          "forced-host mesh; parity is bitwise)")
    for k, v in sharded.items():
        print(f"  {k:34s} {v}")
    print("### speculative decoding (sim step counts + real smoke pair)")
    for k, v in spec.items():
        print(f"  {k:34s} {v}")

    if json_path:
        with open(json_path, "w") as f:
            json.dump(out, f, indent=1)
        print(f"wrote {json_path}")
    return out


if __name__ == "__main__":
    run()
