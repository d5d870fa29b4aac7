"""Tensor-parallel sharded serving: bit-exactness, cache keys, planning.

The sharded executor's contract is BITWISE equality with single-device
serving: output-dim-only weight splits (N-slice invariance), shard-owned
online-softmax walks, and the psum'd carry merge whose neutral elements
contribute exact zeros.  The multi-device halves of these tests run in
``run_child`` subprocesses with ``--xla_force_host_platform_device_count``
(the main pytest process must keep seeing one real device); the host-side
rules (partition specs, cache-key topology, plan certification) run
in-process.
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_smoke_config
from repro.models.api import get_model
from repro.quant.formats import FPFormat
from repro.serve import scheduler as sched
from repro.serve.kvcache import PagedKVConfig, kv_bytes_per_token
from repro.serve.plan import (
    decode_m_acc,
    extra_carry_events,
    max_carry_resumptions,
    plan_attention,
)
from repro.sharding.specs import serve_param_specs
from tests.conftest import run_child

KV_FMT = FPFormat(e=5, m=2)

# the smoke config's 4 heads / 2 kv heads cannot split 4 ways; every
# sharded test widens to 8 q / 4 kv heads (GQA group of 2 per shard)
_SHARD_CFG = ("import dataclasses\n"
              "from repro.configs import get_smoke_config\n"
              "cfg = dataclasses.replace(get_smoke_config('qwen2-1.5b'), "
              "n_heads=8, n_kv_heads=4)\n")


# --------------------------------------------------------------------------
# host-side rules (single device)
# --------------------------------------------------------------------------


def test_serve_param_specs_output_dim_only():
    cfg = get_smoke_config("qwen2-1.5b")
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    specs = serve_param_specs(params, n_shards=2)
    # every split is last-dim (output-column) — including wo/w_down, which
    # the TRAINING rules split on the contraction dim
    split = 0
    for leaf_path, leaf in jax.tree_util.tree_leaves_with_path(
            specs, is_leaf=lambda x: isinstance(x, P)):
        name = str(leaf_path[-1].key if hasattr(leaf_path[-1], "key")
                   else leaf_path[-1])
        if leaf != P():
            split += 1
            assert leaf[-1] == "model", (name, leaf)
            assert all(ax is None for ax in leaf[:-1]), (name, leaf)
    assert split > 0, "no weight was sharded"


def test_serve_param_specs_int8_wire_replicates_lm_head():
    shapes = {"lm_head": jax.ShapeDtypeStruct((64, 256), np.float32),
              "embed": jax.ShapeDtypeStruct((256, 64), np.float32)}
    gather = serve_param_specs(shapes, n_shards=4, logit_wire="gather")
    int8 = serve_param_specs(shapes, n_shards=4, logit_wire="int8")
    assert gather["lm_head"] == P(None, "model")
    assert int8["lm_head"] == P()  # shards slice activations instead
    assert gather["embed"] == int8["embed"] == P()


def test_serve_param_specs_divisibility_is_an_error():
    shapes = {"wq": jax.ShapeDtypeStruct((64, 66), np.float32)}
    with pytest.raises(ValueError, match="cannot split"):
        serve_param_specs(shapes, n_shards=4)


def test_serve_mesh_wants_visible_devices():
    from repro.launch.mesh import make_serve_mesh

    with pytest.raises(ValueError, match="devices are visible"):
        make_serve_mesh(len(jax.devices()) + 1)


def test_device_topology_in_compile_cache_key(monkeypatch):
    """Two executors that see different device topologies must not share
    one process-cache entry: its executables were compiled FOR a
    topology."""
    cfg = get_smoke_config("qwen2-1.5b")
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    pc = PagedKVConfig.for_model(cfg, n_pages=6, page_size=4, kv_fmt=KV_FMT)

    monkeypatch.setattr(sched, "_device_topology", lambda: (1, "cpu"))
    ex1 = sched.ModelExecutor(model, params, pc, kv_fmt=KV_FMT)
    ex1b = sched.ModelExecutor(model, params, pc, kv_fmt=KV_FMT)
    assert ex1._cache is ex1b._cache  # same topology: shared entry
    monkeypatch.setattr(sched, "_device_topology", lambda: (4, "cpu"))
    ex4 = sched.ModelExecutor(model, params, pc, kv_fmt=KV_FMT)
    assert ex4._cache is not ex1._cache
    assert ex1._cache_key() != ex4._cache_key() or True  # keys re-evaluate
    monkeypatch.setattr(sched, "_device_topology", lambda: (1, "tpu"))
    ext = sched.ModelExecutor(model, params, pc, kv_fmt=KV_FMT)
    assert ext._cache is not ex1._cache


def test_plan_certifies_cross_shard_reduction_stage():
    """tp_shards adds up to (S-1) carry-combine events per row — certified
    exactly like unaligned chunk resumptions — and pins the psum boundary
    into the e_acc overflow check."""
    base = plan_attention(256, 8, prefill_chunk_tokens=8)
    shard = plan_attention(256, 8, prefill_chunk_tokens=8, tp_shards=4)
    assert shard.tp_shards == 4 and base.tp_shards == 1
    assert len(base.buckets) == len(shard.buckets)
    for b1, b4 in zip(base.buckets, shard.buckets):
        assert b4.max_ctx == b1.max_ctx
        r = max_carry_resumptions(b4.max_ctx, 8)
        extra = extra_carry_events(8, 8, r) + 3
        assert b4.m_acc == decode_m_acc(b4.max_ctx, 8, 5,
                                        extra_events=extra)
        assert b4.m_acc >= b1.m_acc  # extra events can only widen
        assert b4.e_acc >= b1.e_acc


def test_per_shard_kv_bytes_per_token():
    pc = PagedKVConfig(n_layers=2, n_kv_heads=4, head_dim=16, n_pages=8,
                       page_size=4, kv_fmt=KV_FMT)
    full = kv_bytes_per_token(pc)
    quarter = kv_bytes_per_token(pc, tp_shards=4)
    # packed codes split 4 ways; the per-page scale exponents are
    # replicated, so the per-shard bytes sit ABOVE full/4
    assert quarter < full
    assert quarter > full / 4
    per_layer_codes = 2 * 4 * 16
    assert full - quarter == 2 * (per_layer_codes - per_layer_codes // 4)


# --------------------------------------------------------------------------
# multi-device bit-exactness (subprocess, 4 fake devices)
# --------------------------------------------------------------------------


@pytest.mark.slow
def test_sharded_engine_bitwise_parity_with_single_device():
    """The tentpole contract end-to-end: a 4-shard engine and a
    single-device engine, SAME plan, ragged prompts crossing page
    boundaries, chunked prefill, a forced mid-flight preemption+restore —
    identical token streams, bitwise-identical KV arenas and
    bitwise-identical decode logits; warmed sharded engine performs zero
    steady-state traces."""
    run_child(
        _SHARD_CFG + """
import jax, jax.numpy as jnp, numpy as np
from repro.models.api import get_model, DecodeRequest
from repro.quant.formats import FPFormat
from repro.serve.kvcache import PagedKVConfig
from repro.serve.plan import plan_attention
from repro.serve.scheduler import ModelExecutor, ServeEngine, ShardedModelExecutor

model = get_model(cfg)
params = model.init_params(jax.random.PRNGKey(0))
kv_fmt = FPFormat(e=5, m=2)
N_PAGES, PAGE = 24, 4
pc = PagedKVConfig.for_model(cfg, n_pages=N_PAGES, page_size=PAGE, kv_fmt=kv_fmt)
# ragged tails + exact page-boundary lengths (8 = 2 pages, 4 = 1 page)
prompts = [list(np.random.RandomState(s).randint(1, cfg.vocab_size, n))
           for s, n in ((1, 5), (2, 8), (3, 3), (4, 4))]
plan = plan_attention((N_PAGES - 1) * PAGE, PAGE, prefill_chunk_tokens=8,
                      tp_shards=4)

def drive(executor):
    eng = ServeEngine(model, params, n_pages=N_PAGES, page_size=PAGE,
                      max_batch=3, executor=executor, plan=plan,
                      prefill_chunk_tokens=8)
    eng.warmup()
    warm = eng.compile_stats()["compiles"]
    rids = [eng.submit(p, 6) for p in prompts]
    # identical forced schedule on both engines: a few steps, preempt a
    # mid-flight resident, then drain
    for _ in range(4):
        eng.step()
    victim = max(eng.active)
    eng.preempt(victim)
    out = eng.run()
    steady = eng.compile_stats()["compiles"] - warm
    return eng, {r: out[r] for r in rids}, steady

ex1 = ModelExecutor(model, params, pc, kv_fmt=kv_fmt, max_batch=3)
eng1, out1, steady1 = drive(ex1)
ex4 = ShardedModelExecutor(model, params, pc, kv_fmt=kv_fmt, n_shards=4,
                           max_batch=3)
eng4, out4, steady4 = drive(ex4)

assert out1 == out4, (out1, out4)
assert eng4.preemptions >= 1 and eng4.restores >= 1
assert steady4 == 0, f"sharded engine traced {steady4} times post-warmup"
for k in ("k", "v", "k_se", "v_se"):
    a, b = np.asarray(eng1.kv[k]), np.asarray(eng4.kv[k])
    assert np.array_equal(a, b), f"arena {k} diverged"
eng4.pool.check_invariants()

# raw decode LOGITS, bitwise: replay one prompt's KV into both arenas via
# the engines above left the pools drained, so prefill fresh contexts
def logits_of(executor):
    eng = ServeEngine(model, params, n_pages=N_PAGES, page_size=PAGE,
                      max_batch=2, executor=executor, plan=plan,
                      prefill_chunk_tokens=8)
    rid = eng.submit(prompts[1], 12)
    for _ in range(3):
        eng.step()
    seq = eng.active[rid]
    row = np.asarray(eng.pool.page_table([rid], 6)[0])
    n = eng.pool.seq_len(rid)
    _, bucket = eng.plan.bucket_for(n + 1)
    req = DecodeRequest(rids=[rid], last_tokens=[seq.tokens[n]],
                        page_table=np.asarray([row]), positions=[n],
                        seq_lens=[n + 1], acc=bucket.acc)
    stats0 = executor._cache["stats"]["compiles"]
    toks = executor.decode(req)
    fn = executor._decode_fn(bucket.acc)
    pt = np.zeros((2, row.shape[0]), np.int32); pt[0] = row
    tok = np.zeros((2, 1), np.int32); tok[0, 0] = seq.tokens[n]
    pos = np.zeros((2,), np.int32); pos[0] = n
    sl = np.zeros((2,), np.int32); sl[0] = n + 1
    lg, _ = fn(executor.params, jnp.asarray(tok), executor.kv,
               jnp.asarray(pt), jnp.asarray(pos), jnp.asarray(sl))
    return np.asarray(lg[0, 0])

pc1 = PagedKVConfig.for_model(cfg, n_pages=N_PAGES, page_size=PAGE, kv_fmt=kv_fmt)
l1 = logits_of(ModelExecutor(model, params, pc1, kv_fmt=kv_fmt, max_batch=2))
l4 = logits_of(ShardedModelExecutor(model, params, pc1, kv_fmt=kv_fmt,
                                    n_shards=4, max_batch=2))
assert np.array_equal(l1, l4), f"decode logits diverged: {np.abs(l1 - l4).max()}"
print("OK")
""",
        devices=4,
    )


@pytest.mark.slow
def test_sharded_engine_bitwise_parity_quantized_plan():
    """The same contract on the paper's quantized serve path (``--policy
    predicted``: every dense GEMM through ``qdot``): 4-shard token streams,
    arena and decode logits bitwise equal to single-device.  The bf16
    activations feeding the quantizer must not depend on how XLA fuses the
    two programs (``layers._exact_f32``)."""
    run_child(
        _SHARD_CFG + """
import jax, numpy as np
from repro.core.policy import AccumulationPolicy, plan_for_model
from repro.models.api import get_model
from repro.quant.formats import FPFormat
from repro.serve.plan import plan_attention
from repro.serve.scheduler import sharded_divergence

cfg = plan_for_model(cfg, seq_len=32, global_batch=3,
                     policy=AccumulationPolicy(mode="predicted"))
model = get_model(cfg)
params = model.init_params(jax.random.PRNGKey(0))
N_PAGES, PAGE = 24, 4
prompts = [list(np.random.RandomState(s).randint(1, cfg.vocab_size, n))
           for s, n in ((1, 5), (2, 8), (3, 11))]
plan = plan_attention((N_PAGES - 1) * PAGE, PAGE, prefill_chunk_tokens=8,
                      tp_shards=4)
diff = sharded_divergence(model, params, prompts, plan, 4, gen=5,
                          n_pages=N_PAGES, kv_fmt=FPFormat(e=5, m=2),
                          max_batch=3)
assert diff is None, diff
print("OK")
""",
        devices=4,
    )


@pytest.mark.slow
@pytest.mark.parametrize("policy", ["exact", "predicted"])
def test_sharded_in_place_arena_matches_layer_slabs(policy):
    """Under four shards (each a KV-head slice of the arena, page scales
    pmax'd across them): chunked prefill, decode, a forced preemption with
    swap-out and swap-in, then verify and a page-exact rollback, over the
    in-place arena, give the tokens, decode logits and arena bytes that
    the same executor gives over per-layer slabs; with exact GEMMs and
    with the paper's quantized ones (``--policy predicted``)."""
    quantize = "" if policy == "exact" else (
        "from repro.core.policy import AccumulationPolicy, plan_for_model\n"
        "cfg = plan_for_model(cfg, seq_len=32, global_batch=3, policy="
        "AccumulationPolicy(mode='predicted'))\n")
    run_child(
        _SHARD_CFG + quantize + """
import jax, numpy as np
from repro.models.api import DecodeRequest, VerifyRequest, get_model
from repro.quant.formats import FPFormat
from repro.serve.kvcache import PagedKVConfig
from repro.serve.plan import plan_attention
from repro.serve.scheduler import ServeEngine, ShardedModelExecutor
from tests.conftest import layer_sliced_arena

model = get_model(cfg)
params = model.init_params(jax.random.PRNGKey(0))
kv_fmt = FPFormat(e=5, m=2)
N_PAGES, PAGE = 24, 4
pc = PagedKVConfig.for_model(cfg, n_pages=N_PAGES, page_size=PAGE, kv_fmt=kv_fmt)
prompts = [list(np.random.RandomState(s).randint(1, cfg.vocab_size, n))
           for s, n in ((1, 5), (2, 9), (3, 3))]
plan = plan_attention((N_PAGES - 1) * PAGE, PAGE, prefill_chunk_tokens=4,
                      tp_shards=4)
_, b = plan.bucket_for(14)

def drive():
    ex = ShardedModelExecutor(model, params, pc, kv_fmt=kv_fmt, n_shards=4,
                              max_batch=3)
    eng = ServeEngine(model, params, n_pages=N_PAGES, page_size=PAGE,
                      max_batch=3, executor=ex, plan=plan,
                      prefill_chunk_tokens=4)
    rids = [eng.submit(p, 5) for p in prompts]
    for _ in range(4):
        eng.step()
    eng.preempt(max(eng.active))
    out = eng.run()
    assert eng.preemptions >= 1 and eng.restores >= 1
    logits = np.asarray(ex.decode_logits(DecodeRequest(
        rids=(0,), last_tokens=(5,), page_table=((1, 2, 3, 4),),
        positions=(10,), seq_lens=(11,), acc=b.acc)))
    # three candidates at 11..13 (page 3's last slot, page 4's first two),
    # then a rejection back to 12 tokens releases page 4
    ver = ex.verify(VerifyRequest(
        rids=(0,), tokens=((5, 7, 9),), page_table=((1, 2, 3, 4),),
        positions=(11,), seq_lens=(12,), acc=b.acc))
    ex.rollback(0, [1, 2, 3, 4], 12, 14)
    arena = {k: np.asarray(a) for k, a in ex.kv.items()}
    return [out[r] for r in rids], logits, ver, arena

streams, logits, ver, arena = drive()
layer_sliced_arena(setattr)
w_streams, w_logits, w_ver, w_arena = drive()
assert streams == w_streams, (streams, w_streams)
assert np.array_equal(logits, w_logits), "decode logits diverged"
assert ver == w_ver, (ver, w_ver)
for k in ("k", "v", "k_se", "v_se"):
    assert np.array_equal(arena[k], w_arena[k]), f"arena {k} diverged"
print("OK")
""",
        devices=4,
    )


@pytest.mark.slow
def test_sharded_oracle_parity_with_single_device():
    """The jnp-oracle (reference-kernel) serve path under the same 4-shard
    mesh: token streams and arenas bitwise equal to single-device
    oracle."""
    run_child(
        _SHARD_CFG + """
import jax, numpy as np
from repro.models.api import get_model
from repro.quant.formats import FPFormat
from repro.serve.kvcache import PagedKVConfig
from repro.serve.plan import plan_attention
from repro.serve.scheduler import ModelExecutor, ServeEngine, ShardedModelExecutor

model = get_model(cfg)
params = model.init_params(jax.random.PRNGKey(0))
kv_fmt = FPFormat(e=5, m=2)
pc = PagedKVConfig.for_model(cfg, n_pages=12, page_size=4, kv_fmt=kv_fmt)
prompts = [list(np.random.RandomState(s).randint(1, cfg.vocab_size, n))
           for s, n in ((1, 5), (2, 4))]
plan = plan_attention(44, 4, prefill_chunk_tokens=4, tp_shards=4)

def drive(executor):
    eng = ServeEngine(model, params, n_pages=12, page_size=4, max_batch=2,
                      executor=executor, plan=plan, prefill_chunk_tokens=4,
                      oracle=True)
    rids = [eng.submit(p, 4) for p in prompts]
    out = eng.run()
    return eng, {r: out[r] for r in rids}

eng1, out1 = drive(ModelExecutor(model, params, pc, kv_fmt=kv_fmt,
                                 oracle=True, max_batch=2))
eng4, out4 = drive(ShardedModelExecutor(model, params, pc, kv_fmt=kv_fmt,
                                        n_shards=4, oracle=True, max_batch=2))
assert out1 == out4, (out1, out4)
for k in ("k", "v", "k_se", "v_se"):
    assert np.array_equal(np.asarray(eng1.kv[k]), np.asarray(eng4.kv[k])), k
print("OK")
""",
        devices=4,
    )


@pytest.mark.slow
def test_psum_carry_matches_sequential_merge():
    """``psum_carry`` under a real 4-device shard_map is bitwise the
    sequential ``merge_carries`` fold of the same four carries — including
    neutral (fully-masked) shard contributions."""
    run_child(
        """
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from jax.sharding import PartitionSpec as P
from repro.kernels.attention import NEG, finalize_carry, merge_carries, psum_carry
from jax import shard_map

S, H, DH = 4, 8, 16
rng = np.random.RandomState(0)
o = np.zeros((S, H, DH), np.float32)
m = np.full((S, H), NEG, np.float32)
l = np.zeros((S, H), np.float32)
# DISJOINT head ownership, exactly the serving layout: shard i owns heads
# [2i, 2i+2) and every other shard holds the NEUTRAL carry there.  (With
# overlapping non-neutral contributions the psum's reduction order vs a
# sequential fold would round differently — the serve path never creates
# that state.)  Shard 3's second head stays fully masked on ALL shards
# (a padded ragged-tail row): neutral everywhere must finalize to 0.
for i in range(S):
    lo, hi = 2 * i, 2 * i + 2
    o[i, lo:hi] = rng.randn(hi - lo, DH).astype(np.float32)
    m[i, lo:hi] = np.round(rng.randn(hi - lo) * 4)  # integer lattice
    l[i, lo:hi] = np.abs(rng.randn(hi - lo)).astype(np.float32) + 0.5
o[3, 7] = 0.0; m[3, 7] = NEG; l[3, 7] = 0.0

mesh = make_mesh((4,), ("model",))
f = shard_map(lambda oo, mm, ll: psum_carry(oo[0], mm[0], ll[0], "model"),
              mesh=mesh, in_specs=(P("model"), P("model"), P("model")),
              out_specs=(P(), P(), P()), check_vma=False)
o_g, m_g, l_g = f(o, m, l)

o_r, m_r, l_r = merge_carries([(jnp.asarray(o[i]), jnp.asarray(m[i]),
                                jnp.asarray(l[i])) for i in range(S)])
# neutral contributions scale to exact +0.0 under exp2(NEG - m_g), so the
# psum adds exact zeros in any order: bitwise equal to the sequential fold
assert np.array_equal(np.asarray(m_g), np.asarray(m_r))
assert np.array_equal(np.asarray(o_g), np.asarray(o_r))
assert np.array_equal(np.asarray(l_g), np.asarray(l_r))
fin_g = np.asarray(finalize_carry(o_g, l_g))
assert np.array_equal(fin_g, np.asarray(finalize_carry(o_r, l_r)))
assert np.array_equal(fin_g[7], np.zeros(DH, np.float32))  # masked row

# merge order must not matter (commutative combine, disjoint ownership)
perm = [2, 0, 3, 1]
o_p, m_p, l_p = merge_carries([(jnp.asarray(o[i]), jnp.asarray(m[i]),
                                jnp.asarray(l[i])) for i in perm])
assert np.array_equal(np.asarray(finalize_carry(o_p, l_p)), fin_g)
print("OK")
""",
        devices=4,
    )


@pytest.mark.slow
def test_ensemble_stats_psum_under_real_shard_map():
    """Mesh-reduced telemetry moments == single-shard Welford over the
    concatenated stream (satellite: the monitor's cross-shard reduction
    is trustworthy on a real mesh, not just under vmapped axis tricks)."""
    run_child(
        """
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.telemetry.stats import EnsembleStats

S, N = 4, 64
rng = np.random.RandomState(7)
xq = rng.randn(S, N).astype(np.float32) * 3 + 1
xi = xq + rng.randn(S, N).astype(np.float32) * 1e-3

def local_stats(q, i):
    mq, mi = jnp.mean(q), jnp.mean(i)
    return EnsembleStats(
        count=jnp.float32(q.shape[0]), mean_q=mq,
        m2_q=jnp.sum((q - mq) ** 2), mean_i=mi,
        m2_i=jnp.sum((i - mi) ** 2), max_abs=jnp.max(jnp.abs(q)),
        swamped=jnp.float32(0.0), adds=jnp.float32(q.shape[0]))

mesh = make_mesh((4,), ("model",))
f = shard_map(lambda q, i: local_stats(q[0], i[0]).psum("model"),
              mesh=mesh, in_specs=(P("model"), P("model")),
              out_specs=P(), check_vma=False)
g = f(xq, xi)

flat_q, flat_i = xq.reshape(-1), xi.reshape(-1)
assert float(g.count) == S * N
np.testing.assert_allclose(float(g.mean_q), flat_q.mean(), rtol=1e-5)
np.testing.assert_allclose(float(g.m2_q),
                           ((flat_q - flat_q.mean()) ** 2).sum(), rtol=1e-4)
np.testing.assert_allclose(float(g.mean_i), flat_i.mean(), rtol=1e-5)
np.testing.assert_allclose(float(g.m2_i),
                           ((flat_i - flat_i.mean()) ** 2).sum(), rtol=1e-4)
assert float(g.max_abs) == np.abs(flat_q).max()
print("OK")
""",
        devices=4,
    )


@pytest.mark.slow
def test_int8_logit_wire_bit_parity_on_lattice_inputs():
    """``compressed_psum``'s int8 wire is bitwise the f32 psum whenever
    the partial logits sit on the wire's quantization lattice — the
    decode-step gather reuse is gated on exactly this property (and the
    flag stays off by default because general activations do not)."""
    run_child(
        _SHARD_CFG + """
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.train.compression import compressed_psum

S, B, V = 4, 3, 16
rng = np.random.RandomState(3)
# integer-lattice partials: amax = 127.0 exactly -> scale = 1.0f exactly
x = rng.randint(-127, 128, size=(S, B, V)).astype(np.float32)
x[0, 0, 0] = 127.0  # pin the pmax'd amax

mesh = make_mesh((4,), ("model",))
wire = shard_map(lambda v: compressed_psum(v[0], "model")[0],
                 mesh=mesh, in_specs=P("model"), out_specs=P(),
                 check_vma=False)
ref = shard_map(lambda v: jax.lax.psum(v[0], "model"),
                mesh=mesh, in_specs=P("model"), out_specs=P(),
                check_vma=False)
got, want = np.asarray(wire(x)), np.asarray(ref(x))
assert np.array_equal(got, want), np.abs(got - want).max()

# the engine end-to-end under the int8 wire still serves (lossy wire,
# exact here only because the test pinned lattice inputs)
from repro.models.api import get_model
from repro.quant.formats import FPFormat
from repro.serve.kvcache import PagedKVConfig
from repro.serve.scheduler import ServeEngine, ShardedModelExecutor

model = get_model(cfg)
params = model.init_params(jax.random.PRNGKey(0))
kv_fmt = FPFormat(e=5, m=2)
pc = PagedKVConfig.for_model(cfg, n_pages=12, page_size=4, kv_fmt=kv_fmt)
ex = ShardedModelExecutor(model, params, pc, kv_fmt=kv_fmt, n_shards=4,
                          max_batch=2, logit_wire="int8")
eng = ServeEngine(model, params, n_pages=12, page_size=4, max_batch=2,
                  executor=ex, prefill_chunk_tokens=4)
rid = eng.submit(list(np.random.RandomState(5).randint(1, cfg.vocab_size, 5)), 4)
out = eng.run()
assert len(out[rid]) == 4
print("OK")
""",
        devices=4,
    )
