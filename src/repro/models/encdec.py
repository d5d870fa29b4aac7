"""Encoder-decoder model (Seamless-M4T backbone).

The audio frontend is a stub per the brief: the encoder consumes
pre-computed frame embeddings (B, S_enc, frontend_dim) projected into
d_model.  The decoder is a standard causal stack with cross-attention to
the encoder output.  Training splits the shape budget as
S_enc = S_dec = seq_len // 2 so each (arch x shape) cell keeps the same
token budget as the decoder-only architectures (documented in DESIGN.md).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import layers as L
from repro.models import scan_util
from repro.models.config import ModelConfig
from repro.models import lm
from repro.models.lm import _stack_init, _unembed

Params = dict[str, Any]


def _enc_block_init(key, cfg: ModelConfig) -> Params:
    k1, k2 = jax.random.split(key)
    return {
        "ln1": jnp.ones((cfg.d_model,), jnp.float32),
        "ln2": jnp.ones((cfg.d_model,), jnp.float32),
        "attn": L.attn_init(k1, cfg),
        "mlp": L.mlp_init(k2, cfg),
    }


def _dec_block_init(key, cfg: ModelConfig) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "ln1": jnp.ones((cfg.d_model,), jnp.float32),
        "ln_x": jnp.ones((cfg.d_model,), jnp.float32),
        "ln2": jnp.ones((cfg.d_model,), jnp.float32),
        "attn": L.attn_init(k1, cfg),
        "xattn": L.attn_init(k3, cfg),
        "mlp": L.mlp_init(k2, cfg),
    }


def init_params(key, cfg: ModelConfig) -> Params:
    ke, kf, kenc, kdec, ko = jax.random.split(key, 5)
    return {
        "embed": L._normal(ke, (cfg.vocab_size, cfg.d_model), 1.0 / (cfg.d_model ** 0.5)),
        "frontend_proj": L._normal(kf, (cfg.frontend_dim, cfg.d_model),
                                   1.0 / (cfg.frontend_dim ** 0.5)),
        "encoder": _stack_init(lambda k: _enc_block_init(k, cfg), kenc, cfg.encoder_layers),
        "decoder": _stack_init(lambda k: _dec_block_init(k, cfg), kdec, cfg.n_layers),
        "enc_norm": jnp.ones((cfg.d_model,), jnp.float32),
        "final_norm": jnp.ones((cfg.d_model,), jnp.float32),
        "lm_head": L._normal(ko, (cfg.d_model, cfg.vocab_size), 1.0 / (cfg.d_model ** 0.5)),
    }


def encode(params, frames, cfg: ModelConfig, dist: L.Dist, *, remat: bool = True):
    """frames: (B, S_enc, frontend_dim) -> (B, S_enc, D)."""
    x = L.dense(frames.astype(L.COMPUTE_DTYPE), params["frontend_proj"])
    x = L._constrain(x, dist, P(dist.data_axes, None, None))
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))

    def body(carry, lp):
        h = L.attn_apply(lp["attn"], L.rms_norm(carry, lp["ln1"], cfg.norm_eps),
                         cfg, dist, positions=positions, causal=False)
        carry = carry + h
        z = L.rms_norm(carry, lp["ln2"], cfg.norm_eps)
        return carry + L.mlp_apply(lp["mlp"], z, cfg), None

    if remat:
        body = lm._remat(body)
    x, _ = scan_util.scan(body, x, params["encoder"])
    return L.rms_norm(x, params["enc_norm"], cfg.norm_eps)


def _dec_block_apply(lp, x, enc_out, cfg, dist, positions):
    h = L.attn_apply(lp["attn"], L.rms_norm(x, lp["ln1"], cfg.norm_eps),
                     cfg, dist, positions=positions)
    x = x + h
    h = L.attn_apply(lp["xattn"], L.rms_norm(x, lp["ln_x"], cfg.norm_eps),
                     cfg, dist, positions=positions, context=enc_out)
    x = x + h
    z = L.rms_norm(x, lp["ln2"], cfg.norm_eps)
    return x + L.mlp_apply(lp["mlp"], z, cfg)


def forward(params, batch, cfg: ModelConfig, dist: L.Dist = L.LOCAL, *,
            remat: bool = True):
    """batch: {"frames": (B,S_enc,F), "tokens": (B,S_dec)} -> (logits, aux)."""
    enc_out = encode(params, batch["frames"], cfg, dist, remat=remat)
    tokens = batch["tokens"]
    b, s = tokens.shape
    x = params["embed"][tokens].astype(L.COMPUTE_DTYPE)
    x = L._constrain(x, dist, P(dist.data_axes, None, None))
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))

    def body(carry, lp):
        return _dec_block_apply(lp, carry, enc_out, cfg, dist, positions), None

    if remat:
        body = lm._remat(body)
    x, _ = scan_util.scan(body, x, params["decoder"])
    logits = _unembed(params, x, cfg, dist)
    return logits, jnp.zeros((), jnp.float32)


def prefill(params, batch, cfg: ModelConfig, dist: L.Dist = L.LOCAL):
    """Serving prefill: encoder pass + teacher-forced decoder pass, emitting
    next-token logits for the last decoder position only."""
    enc_out = encode(params, batch["frames"], cfg, dist, remat=False)
    tokens = batch["tokens"]
    b, s = tokens.shape
    x = params["embed"][tokens].astype(L.COMPUTE_DTYPE)
    x = L._constrain(x, dist, P(dist.data_axes, None, None))
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))

    def body(carry, lp):
        return _dec_block_apply(lp, carry, enc_out, cfg, dist, positions), None

    x, _ = scan_util.scan(body, x, params["decoder"])
    return _unembed(params, x[:, -1:], cfg, dist)[:, 0]


def loss_fn(params, batch, cfg: ModelConfig, dist: L.Dist = L.LOCAL, *,
            remat: bool = True):
    logits, _ = forward(params, batch, cfg, dist, remat=remat)
    tgt = batch["tokens"][:, 1:]
    lg = logits[:, :-1].astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, tgt[..., None], axis=-1)[..., 0]
    loss = jnp.mean(lse - gold)
    return loss, {"ce": loss}


# ----------------------------- decode path --------------------------------


def init_decode_state(cfg: ModelConfig, batch: int, max_t: int, enc_len: int) -> Params:
    mk = lambda _: L.attn_cache_init(cfg, batch, max_t)  # noqa: E731
    return {
        "layers": jax.vmap(mk)(jnp.arange(cfg.n_layers)),
        # cross-attention K/V are computed once from the encoder output at
        # prefill time and stay fixed during decode
        "xk": jnp.zeros((cfg.n_layers, batch, enc_len, cfg.n_kv_heads, cfg.head_dim),
                        L.COMPUTE_DTYPE),
        "xv": jnp.zeros((cfg.n_layers, batch, enc_len, cfg.n_kv_heads, cfg.head_dim),
                        L.COMPUTE_DTYPE),
    }


def prime_cross_attention(params, enc_out, cfg: ModelConfig, state: Params) -> Params:
    """Project encoder output through each decoder layer's cross-attn K/V."""
    b, s, _ = enc_out.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))

    def body(_, lp):
        k, v = L._kv_proj(lp["xattn"], enc_out, cfg, positions)
        return None, (k.astype(L.COMPUTE_DTYPE), v.astype(L.COMPUTE_DTYPE))

    _, (xk, xv) = scan_util.scan(body, None, params["decoder"])
    return {**state, "xk": xk, "xv": xv}


def init_paged_state(cfg: ModelConfig, *, n_pages: int, page_size: int,
                     kv_fmt=None) -> dict:
    """Deprecated: use ``models.api.paged_init_state``.  (The paged arena
    serves the decoder's SELF-attention only — cross-attention K/V stay a
    dense prefill-time projection: encoder-length, fixed, shared-shape
    across the batch, so paging buys nothing there.)"""
    from repro.models.api import paged_init_state  # late: api imports encdec

    return paged_init_state(cfg, n_pages=n_pages, page_size=page_size,
                            kv_fmt=kv_fmt)


def paged_decode(params, tokens, kv_state, xk, xv, page_table,
                 positions, seq_lens, cfg: ModelConfig,
                 dist: L.Dist = L.LOCAL, *, kv_fmt,
                 acc: tuple[int, int], oracle: bool = False):
    """One decoder token through the paged self-attention cache (the serve
    subsystem's cache + flash-decode kernel) with fixed cross-attention
    memory ``xk``/``xv`` ((L, B, T_enc, KV, dh), from
    ``prime_cross_attention``).  Per-sequence ``positions``/``seq_lens`` as
    in ``repro.models.lm.paged_decode``."""
    x = params["embed"][tokens].astype(L.COMPUTE_DTYPE)
    x = L._constrain(x, dist, P(dist.data_axes, None, None))

    def body(carry, inp):
        (x, kv), (lp, xkl, xvl, layer) = carry, inp
        h, kv = L.attn_decode_paged(
            lp["attn"], L.rms_norm(x, lp["ln1"], cfg.norm_eps), kv,
            page_table, positions, seq_lens, cfg, dist,
            layer=layer, kv_fmt=kv_fmt, acc=acc, oracle=oracle)
        x = x + h
        z = L.rms_norm(x, lp["ln_x"], cfg.norm_eps)
        q = L._q_proj(lp["xattn"], z, cfg, positions[:, None])
        o = L._gqa_attend(q, xkl, xvl, None, cfg, dist)
        x = x + L.dense(o, lp["xattn"]["wo"], cfg.quant.attn_out)
        z = L.rms_norm(x, lp["ln2"], cfg.norm_eps)
        return (x + L.mlp_apply(lp["mlp"], z, cfg), kv), None

    # the arena rides the carry whole, written and read at each layer's
    # index in place (as ``lm._paged_stack``)
    layers = jnp.arange(kv_state["k"].shape[0], dtype=jnp.int32)
    (x, new_kv), _ = scan_util.scan(body, (x, kv_state),
                                    (params["decoder"], xk, xv, layers))
    logits = _unembed(params, x, cfg, dist)
    return logits, new_kv


def decode_step(params, tokens, state, pos, cfg: ModelConfig,
                dist: L.Dist = L.LOCAL):
    """One decoder token with fixed cross-attention memory."""
    x = params["embed"][tokens].astype(L.COMPUTE_DTYPE)
    x = L._constrain(x, dist, P(dist.data_axes, None, None))

    def body(carry, inp):
        lp, cache, xk, xv = inp
        h, nc = L.attn_decode(lp["attn"], L.rms_norm(carry, lp["ln1"], cfg.norm_eps),
                              cache, pos, cfg, dist)
        carry = carry + h
        z = L.rms_norm(carry, lp["ln_x"], cfg.norm_eps)
        b = z.shape[0]
        positions = jnp.full((b, 1), pos, jnp.int32)
        q = L._q_proj(lp["xattn"], z, cfg, positions)
        o = L._gqa_attend(q, xk, xv, None, cfg, dist)
        carry = carry + L.dense(o, lp["xattn"]["wo"], cfg.quant.attn_out)
        z = L.rms_norm(carry, lp["ln2"], cfg.norm_eps)
        return carry + L.mlp_apply(lp["mlp"], z, cfg), nc

    x, caches = scan_util.scan(
        body, x, (params["decoder"], state["layers"], state["xk"], state["xv"])
    )
    logits = _unembed(params, x, cfg, dist)
    return logits, {**state, "layers": caches}
