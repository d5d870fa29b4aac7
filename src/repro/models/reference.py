"""Plain dense-decoder reference: the forward pass of one sequence in f32
``jax.numpy`` under ``jax.default_matmul_precision("highest")``, with no
kernels, cache, batching, quantisation or sharding.

It covers both dense families the system serves, switched by the
program's ``ModelConfig``:

* Qwen2 (arXiv:2407.10671): QKV bias (``attn_bias``), tied head
  (``tie_embeddings``);
* Qwen3 (arXiv:2505.09388): no QKV bias, RMSNorm over ``head_dim`` on q
  and on k before RoPE (``qk_norm``), untied ``lm_head``.

It takes the program's parameter tree as ``models.lm.init_params`` lays
it out (any float dtype; every weight is read as f32) and does what the
published decoder does: pre-norm blocks of grouped-query causal attention
and a SwiGLU MLP, a final RMSNorm and the output head.  Departures from
the published models:

* RoPE rotates the two halves of each head (``rotate_half``, as the
  published code does) with the plain inverse-frequency table: no
  scaling (neither model's ``config.json`` asks for one);
* no sliding-window attention (both configs set ``use_sliding_window``
  false) and no attention or residual dropout (inference);
* weights are whatever the caller holds (the program serves bf16); the
  reference computes in f32 from them, so it does not round activations
  to bf16 between layers as a bf16 deployment does.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

__all__ = ["hidden", "logits"]


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * _f32(scale)


def _rope(x, pos, theta):
    """x (S, H, dh) rotated at positions ``pos`` (S,)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(cfg, x, lp, pos):
    s = x.shape[0]
    h, kv, dh, eps = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.norm_eps
    ap, mp = lp["attn"], lp["mlp"]

    def proj(a, w, b):
        y = a @ _f32(ap[w])
        return y + _f32(ap[b]) if cfg.attn_bias else y

    a = _rms(x, lp["ln1"], eps)
    q = proj(a, "wq", "bq").reshape(s, h, dh)
    k = proj(a, "wk", "bk").reshape(s, kv, dh)
    v = proj(a, "wv", "bv").reshape(s, kv, dh)
    if cfg.qk_norm:
        q, k = _rms(q, ap["q_norm"], eps), _rms(k, ap["k_norm"], eps)
    q, k = _rope(q, pos, cfg.rope_theta), _rope(k, pos, cfg.rope_theta)
    q = q.reshape(s, kv, h // kv, dh)
    sc = jnp.einsum("skgd,tkd->kgst", q, k) / math.sqrt(dh)
    causal = pos[:, None] >= pos[None, :]
    p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
    o = jnp.einsum("kgst,tkd->skgd", p, v).reshape(s, h * dh)
    x = x + o @ _f32(ap["wo"])
    b = _rms(x, lp["ln2"], eps)
    g = jax.nn.silu(b @ _f32(mp["w_gate"])) * (b @ _f32(mp["w_up"]))
    return x + g @ _f32(mp["w_down"])


def hidden(cfg, params, tokens):
    """Final normed hidden states (S, d_model) of one sequence ``tokens``
    (S,) int."""
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens)
        pos = jnp.arange(tokens.shape[0])
        x = _f32(params["embed"])[tokens]

        def body(x, lp):
            return _layer(cfg, x, lp, pos), None

        x, _ = jax.lax.scan(body, x, params["layers"])
        return _rms(x, params["final_norm"], cfg.norm_eps)


def logits(cfg, params, tokens):
    """Logits (S, vocab) of one sequence: row ``i`` is the next-token
    distribution after ``tokens[:i + 1]``."""
    x = hidden(cfg, params, tokens)
    head = (_f32(params["embed"]).T if cfg.tie_embeddings
            else _f32(params["lm_head"]))
    with jax.default_matmul_precision("highest"):
        return x @ head
