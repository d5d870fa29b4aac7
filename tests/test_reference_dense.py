"""The served path against the plain reference (``repro.models.reference``).

Requests go through ``ServeEngine`` over the paged arena: chunked prefill
(the final slab's logits) and then batched decode, step by step (each
decode call's logits), on seeded random weights whose norm scales are not
1 and whose biases are not 0.  Each served logit row is compared with the
row of the reference's full forward pass over the same tokens, as
``rel_rms``: the RMS of the difference over the RMS of the reference
row's spread about its mean, over every compared row.

The Qwen3-shaped model keeps the published group of 4 query heads per KV
head at 16 query and 4 KV heads, so that it splits 4 ways.
"""

from __future__ import annotations

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.core.policy import AccumulationPolicy, plan_for_model
from repro.launch.serve import _init_params
from repro.models import reference as R
from repro.models.api import get_model
from repro.quant.formats import FPFormat
from repro.serve.scheduler import ServeEngine
from tests.conftest import run_child

# ``--policy exact``: the GEMMs accumulate wide, but the arena holds K and
# V as (1,5,2) codes under a power-of-two page scale (a relative rounding
# error of up to 2^-3 per element) and activations are bf16.  Those alone
# read 0.09 here; 0.15 leaves room above that and stays well below the
# 0.33 that leaving qk-norm out reads.
EXACT_TOL = 0.15
# ``--policy predicted``: (1,5,2) GEMM operands and certified narrow
# accumulators on top.  They read 0.21 here; the same program with
# operands one mantissa bit narrower, (1,5,1), reads 0.44 and must fail.
PREDICTED_TOL = 0.3

QWEN3 = dataclasses.replace(
    get_config("qwen3-8b"), n_layers=2, d_model=64, n_heads=16,
    n_kv_heads=4, d_head=16, d_ff=128, vocab_size=256, norm_eps=1e-6)
PROMPTS = (21, 40)
GEN = 8


def planned(base, policy: str, operand_m: int = 2):
    cfg = plan_for_model(base, seq_len=64, global_batch=2,
                         policy=AccumulationPolicy(mode=policy))
    if operand_m == 2 or policy == "exact":
        return cfg
    q = cfg.quant

    def narrow(c):
        return dataclasses.replace(c, repr_fmt=FPFormat(e=5, m=operand_m))

    return dataclasses.replace(cfg, quant=dataclasses.replace(
        q, attn_qkv=narrow(q.attn_qkv), attn_out=narrow(q.attn_out),
        mlp_up=narrow(q.mlp_up), mlp_down=narrow(q.mlp_down)))


def weights(cfg, mesh=None):
    """The program's bf16 serving weights, with norm scales 1 + N(0, 0.2)
    and biases N(0, 0.1) so that both matter."""
    params = _init_params(get_model(cfg), jax.random.PRNGKey(3), mesh=mesh)
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(4), len(leaves))
    out = []
    for (path, x), k in zip(leaves, keys):
        name = str(path[-1].key)
        if name in ("ln1", "ln2", "final_norm", "q_norm", "k_norm"):
            x = (1.0 + 0.2 * jax.random.normal(k, x.shape)).astype(x.dtype)
        elif name in ("bq", "bk", "bv"):
            x = (0.1 * jax.random.normal(k, x.shape)).astype(x.dtype)
        out.append(x)
    return jax.tree.unflatten(tree, out)


def serve(cfg, params, executor=None):
    """Serve ``PROMPTS`` for ``GEN`` tokens each; returns {rid: (tokens
    fed, [(position, logits row)])}: the final prefill slab's row and
    every decode call's."""
    eng = ServeEngine(get_model(cfg), params, n_pages=33, page_size=8,
                      max_batch=4, prefill_chunk_tokens=16,
                      executor=executor)
    ex = eng.executor
    rows: dict[int, list] = {}
    real_prefill, real_fn, real_decode = (ex.prefill, ex._prefill_fn,
                                          ex.decode_logits)
    now = {}

    def prefill(req):
        now["req"] = req
        return real_prefill(req)

    def prefill_fn(acc, final, call=None):
        fn = real_fn(acc, final, call)

        def recorded(*a):
            lg, kv = fn(*a)
            req = now["req"]
            rows.setdefault(req.rid, []).append(
                (req.t0 + len(req.tokens) - 1, np.asarray(lg[0], np.float32)))
            return lg, kv
        return recorded if final else fn

    def decode_logits(req):
        lg = real_decode(req)
        for i, rid in enumerate(req.rids):
            rows[rid].append((req.positions[i],
                              np.asarray(lg[i, 0], np.float32)))
        return lg

    ex.prefill, ex._prefill_fn, ex.decode_logits = (prefill, prefill_fn,
                                                    decode_logits)
    rng = np.random.RandomState(0)
    prompts = [[int(t) for t in rng.randint(1, cfg.vocab_size, n)]
               for n in PROMPTS]
    rids = [eng.submit(p, GEN) for p in prompts]
    out = eng.run()
    return {rid: (p + out[rid][:-1], rows[rid])
            for rid, p in zip(rids, prompts)}


def rel_rms(ref_cfg, params, served) -> float:
    err, spread = [], []
    for toks, rows in served.values():
        ref = np.asarray(R.logits(ref_cfg, params, jnp.asarray(toks)))
        for pos, got in rows:
            err.append(got - ref[pos])
            spread.append(ref[pos] - ref[pos].mean())
    err, spread = np.stack(err), np.stack(spread)
    return float(np.sqrt(np.mean(err ** 2)) / np.sqrt(np.mean(spread ** 2)))


@pytest.fixture(scope="module")
def exact_run():
    cfg = planned(QWEN3, "exact")
    params = weights(cfg)
    return cfg, params, serve(cfg, params)


def test_exact_prefill_and_decode_match_reference(exact_run):
    cfg, params, served = exact_run
    assert sum(len(rows) for _, rows in served.values()) == \
        len(PROMPTS) * GEN
    assert rel_rms(cfg, params, served) <= EXACT_TOL


def test_reference_without_qk_norm_fails(exact_run):
    cfg, params, served = exact_run
    assert rel_rms(dataclasses.replace(cfg, qk_norm=False), params,
                   served) > EXACT_TOL


@pytest.mark.parametrize("operand_m,passes", [(2, True), (1, False)])
def test_predicted_matches_reference_and_narrower_operands_fail(
        operand_m, passes):
    cfg = planned(QWEN3, "predicted", operand_m)
    params = weights(cfg)
    got = rel_rms(cfg, params, serve(cfg, params))
    assert (got <= PREDICTED_TOL) == passes, got


def test_qwen2_bias_and_tied_head_match_reference():
    base = get_smoke_config("qwen2-1.5b")
    assert base.attn_bias and base.tie_embeddings and not base.qk_norm
    cfg = planned(dataclasses.replace(base, norm_eps=1e-6), "exact")
    params = weights(cfg)
    assert "lm_head" not in params and "bq" in params["layers"]["attn"]
    assert rel_rms(cfg, params, serve(cfg, params)) <= EXACT_TOL


_SHARDED = r'''
import json
from repro.launch.mesh import make_serve_mesh
from repro.quant.formats import FPFormat
from repro.serve.kvcache import PagedKVConfig
from repro.serve.scheduler import ShardedModelExecutor
from tests.test_reference_dense import (QWEN3, planned, weights, serve,
                                        rel_rms)
from repro.models.api import get_model

cfg = planned(QWEN3, "predicted")
mesh = make_serve_mesh(4)
params = weights(cfg, mesh=mesh)
kv = FPFormat(e=5, m=2)
pc = PagedKVConfig.for_model(cfg, n_pages=33, page_size=8, kv_fmt=kv)
ex = ShardedModelExecutor(get_model(cfg), params, pc, kv_fmt=kv, mesh=mesh,
                          max_batch=4, logit_wire="gather")
served = serve(cfg, params, executor=ex)
print(json.dumps({"rel_rms": rel_rms(cfg, params, served),
                  "rows": sum(len(r) for _, r in served.values())}))
'''


def test_sharded_executor_matches_reference():
    out = json.loads(run_child(_SHARDED, devices=4).strip().splitlines()[-1])
    assert out["rows"] == len(PROMPTS) * GEN
    assert out["rel_rms"] <= PREDICTED_TOL
