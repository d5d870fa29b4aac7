"""The tensor-parallel executor's instrumentation: its ``executor.*`` spans
carry ``shards`` and ``collective_bytes``, the metrics registry counts the
same bytes by kind, the byte count agrees with the collectives in the
traced programs, and the page pool's lockstep checks run in
``serve.pool_lockstep`` spans.  Four forced CPU devices, in a child."""

from __future__ import annotations

import dataclasses
import json

from repro.configs import get_config
from repro.models.layers import LOCAL, Dist
from repro.models.lm import paged_collective_bytes
from tests.conftest import run_child

_CHILD = r'''
import dataclasses, json
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.core.policy import AccumulationPolicy, plan_for_model
from repro.launch.mesh import make_serve_mesh
from repro.launch.serve import _init_params
from repro.models.api import get_model
from repro.models.lm import paged_collective_bytes
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import Tracer
from repro.quant.formats import FPFormat
from repro.serve.kvcache import PagedKVConfig
from repro.serve.scheduler import ServeEngine, ShardedModelExecutor

KV = FPFormat(e=5, m=2)
base = dataclasses.replace(get_config("qwen3-8b"), n_layers=2, d_model=64,
                           n_heads=16, n_kv_heads=4, d_head=16, d_ff=128,
                           vocab_size=256)
cfg = plan_for_model(base, seq_len=64, global_batch=2,
                     policy=AccumulationPolicy(mode="predicted"))
model = get_model(cfg)
mesh = make_serve_mesh(4)
params = _init_params(model, jax.random.PRNGKey(0), mesh=mesh)
pc = PagedKVConfig.for_model(cfg, n_pages=33, page_size=8, kv_fmt=KV)
ex = ShardedModelExecutor(model, params, pc, kv_fmt=KV, mesh=mesh,
                          max_batch=4)
tracer, reg = Tracer(), MetricsRegistry()
eng = ServeEngine(model, params, n_pages=33, page_size=8, max_batch=4,
                  prefill_chunk_tokens=16, executor=ex, tracer=tracer,
                  metrics=reg)
rng = np.random.RandomState(0)
for n in (21, 40):
    eng.submit(list(rng.randint(1, 256, n)), 6)
eng.run()
spans = [s.to_dict() for s in tracer.spans]
ex_spans = [[s["name"], s["attrs"]] for s in spans
            if s["name"] in ("executor.decode", "executor.prefill")]
counter = reg.counter("repro_serve_collective_bytes_total",
                      labels=("kind",))
kinds = {k: counter.value(kind=k)
         for k in ("carry", "gather", "scale", "logits")}
lockstep = sum(s["name"] == "serve.pool_lockstep" for s in spans)


def traced_bytes(fn, *args):
    """Result bytes of every collective in the jaxpr, on one shard; a
    scan body's counted once per iteration."""
    total = 0

    def walk(j, mult):
        nonlocal total
        for e in j.eqns:
            if e.primitive.name in ("psum", "pmax", "all_gather"):
                total += mult * sum(int(np.prod(v.aval.shape))
                                    * v.aval.dtype.itemsize
                                    for v in e.outvars)
            inner = mult * (e.params.get("length", 1)
                            if e.primitive.name == "scan" else 1)
            for v in e.params.values():
                for s in (v if isinstance(v, (list, tuple)) else [v]):
                    sub = getattr(s, "jaxpr", s)
                    if hasattr(sub, "eqns"):
                        walk(sub, inner)

    walk(jax.make_jaxpr(fn)(*args).jaxpr, 1)
    return total


i32 = jnp.int32
acc = eng.plan.buckets[-1].acc
w = eng.plan.buckets[-1].max_pages(8)
dec = traced_bytes(ex._decode_fn(acc), ex.params, jnp.zeros((4, 1), i32),
                   ex.kv, jnp.zeros((4, w), i32), jnp.zeros((4,), i32),
                   jnp.zeros((4,), i32))
pre = traced_bytes(ex._prefill_fn(acc, True), ex.params,
                   jnp.zeros((1, 16), i32), ex.kv, jnp.zeros((w,), i32),
                   jnp.zeros((2,), i32), i32(0), i32(16))
ex8 = ShardedModelExecutor(model, params, pc, kv_fmt=KV, mesh=mesh,
                           max_batch=4, logit_wire="int8")
dec8 = traced_bytes(ex8._decode_fn(acc), ex8.params, jnp.zeros((4, 1), i32),
                    ex8.kv, jnp.zeros((4, w), i32), jnp.zeros((4,), i32),
                    jnp.zeros((4,), i32))
want = [sum(paged_collective_bytes(cfg, e.dist, rows=r, scale_rows=sr,
                                   logit_rows=lr).values())
        for e, r, sr, lr in ((ex, 4, 4, 4), (ex, 16, 2, 1), (ex8, 4, 4, 4))]
print(json.dumps({"spans": ex_spans, "kinds": kinds, "lockstep": lockstep,
                  "traced": [dec, pre, dec8], "formula": want}))
'''


def test_sharded_spans_and_counter_carry_collective_bytes():
    out = json.loads(run_child(_CHILD, devices=4).strip().splitlines()[-1])
    spans = out["spans"]
    assert {n for n, _ in spans} == {"executor.decode", "executor.prefill"}
    for _, attrs in spans:
        assert attrs["shards"] == 4
        assert attrs["collective_bytes"] > 0
    # the registry counts exactly what the spans carry
    assert sum(out["kinds"].values()) == sum(a["collective_bytes"]
                                            for _, a in spans)
    assert all(v > 0 for v in out["kinds"].values()), out["kinds"]
    # the byte count from shapes is the collectives the programs trace:
    # decode and prefill under the gather wire, decode under the int8 one
    assert out["traced"] == out["formula"]
    assert out["lockstep"] > 0


def test_collective_bytes_by_kind_from_shapes():
    cfg = get_config("qwen3-8b")
    tp = Dist(shard_axis="model", tp_size=4)
    got = paged_collective_bytes(cfg, tp, rows=32, scale_rows=32,
                                 logit_rows=32)
    # per layer: f32 carry over all 32 heads of 128 (+ m and l), bf16
    # gathers of d_model, d_ff and d_model, two f32 page-scale pmaxes;
    # then the untied head's bf16 vocab gather
    assert got == {"carry": 36 * 32 * 32 * 130 * 4,
                   "gather": 36 * 32 * (2 * 4096 + 12288) * 2,
                   "scale": 36 * 2 * 32 * 4,
                   "logits": 32 * 151936 * 2}
    assert set(paged_collective_bytes(cfg, LOCAL, rows=32, scale_rows=32,
                                      logit_rows=32).values()) == {0}
    tied = dataclasses.replace(cfg, tie_embeddings=True)
    assert paged_collective_bytes(tied, tp, rows=32, scale_rows=32,
                                  logit_rows=32)["logits"] == 0
