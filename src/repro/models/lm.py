"""Decoder-only language models (dense / moe / ssm / hybrid / vlm).

Layer stacks are homogeneous pytrees with a leading layer axis, applied with
``lax.scan`` (+ remat in training) so the HLO stays compact at 512 devices.
The hybrid (Zamba-2) pattern — a single *shared* attention block applied
after every k SSM layers — is a python loop of scanned sub-stacks.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import layers as L
from repro.models import scan_util
from repro.models.config import ModelConfig

Params = dict[str, Any]


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------


def _stack_init(init_fn, key, n: int) -> Params:
    return jax.vmap(init_fn)(jax.random.split(key, n))


def _block_init(key, cfg: ModelConfig) -> Params:
    """One decoder block of the dense/moe family."""
    k1, k2 = jax.random.split(key)
    p: Params = {
        "ln1": jnp.ones((cfg.d_model,), jnp.float32),
        "ln2": jnp.ones((cfg.d_model,), jnp.float32),
        "attn": L.attn_init(k1, cfg),
    }
    if cfg.moe is not None:
        p["moe"] = L.moe_init(k2, cfg)
    else:
        p["mlp"] = L.mlp_init(k2, cfg)
    return p


def _mamba_block_init(key, cfg: ModelConfig) -> Params:
    return {
        "ln": jnp.ones((cfg.d_model,), jnp.float32),
        "mamba": L.mamba_init(key, cfg),
    }


def init_params(key, cfg: ModelConfig) -> Params:
    ke, kl, kh, ko = jax.random.split(key, 4)
    p: Params = {
        # std d^-1/2 keeps tied-head logits O(1) at init
        "embed": L._normal(ke, (cfg.vocab_size, cfg.d_model), 1.0 / (cfg.d_model ** 0.5)),
        "final_norm": jnp.ones((cfg.d_model,), jnp.float32),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = L._normal(ko, (cfg.d_model, cfg.vocab_size),
                                 1.0 / (cfg.d_model ** 0.5))
    if cfg.family in ("dense", "moe", "vlm"):
        p["layers"] = _stack_init(lambda k: _block_init(k, cfg), kl, cfg.n_layers)
    elif cfg.family == "ssm":
        p["layers"] = _stack_init(lambda k: _mamba_block_init(k, cfg), kl, cfg.n_layers)
    elif cfg.family == "hybrid":
        p["layers"] = _stack_init(lambda k: _mamba_block_init(k, cfg), kl, cfg.n_layers)
        p["shared_block"] = _block_init(kh, cfg)
    else:
        raise ValueError(cfg.family)
    if cfg.vision_tokens:
        # vision stub: a frozen-shape projection exists in the real model;
        # patch embeddings arrive pre-computed via input_specs.
        pass
    return p


# --------------------------------------------------------------------------
# blocks
# --------------------------------------------------------------------------


def _block_apply(bp: Params, x, cfg: ModelConfig, dist: L.Dist, positions):
    """attn(+moe/mlp) block, pre-norm residual.  Returns (y, aux)."""
    h = L.attn_apply(bp["attn"], L.rms_norm(x, bp["ln1"], cfg.norm_eps), cfg, dist,
                     positions=positions)
    x = x + h
    z = L.rms_norm(x, bp["ln2"], cfg.norm_eps)
    if cfg.moe is not None and "moe" in bp:
        f, aux = L.moe_apply(bp["moe"], z, cfg, dist)
    else:
        f, aux = L.mlp_apply(bp["mlp"], z, cfg), jnp.zeros((), jnp.float32)
    return x + f, aux


def _mamba_block_apply(bp: Params, x, cfg: ModelConfig, dist: L.Dist):
    return x + L.mamba_apply(bp["mamba"], L.rms_norm(x, bp["ln"], cfg.norm_eps), cfg, dist)


def _remat(body):
    """Remat policy knob (REPRO_REMAT_POLICY): 'full' (default) recomputes
    the whole block body; 'dots' saves matmul outputs and recomputes only
    elementwise ops (-~24% HLO FLOPs for +resident activations — §Perf);
    'none' disables remat (smoke scale)."""
    import os

    pol = os.environ.get("REPRO_REMAT_POLICY", "full")
    if pol == "none":
        return body
    if pol == "dots":
        return jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(body)


def _scan_blocks(stacked: Params, x, cfg, dist, positions, *, remat: bool):
    """Scan a homogeneous stack of attention blocks over the layer axis."""

    def body(carry, lp):
        y, aux = _block_apply(lp, carry, cfg, dist, positions)
        return y, aux

    if remat:
        body = _remat(body)
    x, auxs = scan_util.scan(body, x, stacked)
    return x, jnp.sum(auxs)


def _scan_mamba(stacked: Params, x, cfg, dist, *, remat: bool):
    def body(carry, lp):
        return _mamba_block_apply(lp, carry, cfg, dist), None

    if remat:
        body = _remat(body)
    x, _ = scan_util.scan(body, x, stacked)
    return x


# --------------------------------------------------------------------------
# forward (train / prefill)
# --------------------------------------------------------------------------


def _embed(params, tokens, cfg: ModelConfig, dist: L.Dist, batch: dict):
    x = params["embed"][tokens].astype(L.COMPUTE_DTYPE)
    if cfg.vision_tokens:
        pe = batch["patch_embeds"].astype(L.COMPUTE_DTYPE)
        x = jnp.concatenate([pe, x[:, cfg.vision_tokens:]], axis=1)
    x = L._constrain(x, dist, P(dist.data_axes, None, None))
    return x


def _unembed(params, x, cfg: ModelConfig, dist: L.Dist):
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    if dist.shard_axis is not None:
        return _unembed_sharded(x, head, cfg, dist)
    logits = L.dense(x, head, cfg.quant.lm_head)
    return L._constrain(logits, dist, P(dist.data_axes, None, "model"))


def _unembed_sharded(x, head, cfg: ModelConfig, dist: L.Dist):
    """Tensor-parallel logit gather (inside the serve ``shard_map``).

    ``logit_wire="gather"``: with tied embeddings the head is replicated
    and the dot is fully local (trivially exact); an untied ``lm_head``
    is vocab-split and the local logits are all_gathered (pure movement,
    exact).  ``logit_wire="int8"`` reuses the training DCN idiom
    (``train.compression.compressed_psum``): the head stays replicated,
    each shard computes partial logits over its d_model slice, and the
    partials cross the wire as int8 codes under a pmax-shared scale —
    int8 codes sum exactly in int32, so the only loss is the one
    quantization of each partial, priced by the bit-parity test against
    the f32 psum."""
    ax = dist.shard_axis
    if dist.logit_wire == "int8":
        from repro.train.compression import compressed_psum  # late: circular

        d = head.shape[0]
        d_loc = d // dist.tp_size
        i = jax.lax.axis_index(ax)
        x_l = jax.lax.dynamic_slice_in_dim(x, i * d_loc, d_loc, axis=x.ndim - 1)
        h_l = jax.lax.dynamic_slice_in_dim(head, i * d_loc, d_loc, axis=0)
        part = L.dense(x_l, h_l, cfg.quant.lm_head).astype(jnp.float32)
        logits, _ = compressed_psum(part, ax)
        return logits.astype(L.COMPUTE_DTYPE)
    logits = L.dense(x, head, cfg.quant.lm_head)
    if not cfg.tie_embeddings:
        logits = L._gather_cols(logits, dist)
    return logits


def forward_hidden(
    params: Params,
    batch: dict,
    cfg: ModelConfig,
    dist: L.Dist = L.LOCAL,
    *,
    remat: bool = True,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Full-sequence forward up to the final hidden state -> (x, aux)."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    x = _embed(params, tokens, cfg, dist, batch)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    aux = jnp.zeros((), jnp.float32)

    if cfg.family in ("dense", "moe", "vlm"):
        x, aux = _scan_blocks(params["layers"], x, cfg, dist, positions, remat=remat)
    elif cfg.family == "ssm":
        x = _scan_mamba(params["layers"], x, cfg, dist, remat=remat)
    elif cfg.family == "hybrid":
        k = cfg.hybrid_attn_every
        n = cfg.n_layers
        shared = params["shared_block"]
        for u in range(0, n, k):
            hi = min(u + k, n)
            sub = jax.tree.map(lambda a: a[u:hi], params["layers"])
            x = _scan_mamba(sub, x, cfg, dist, remat=remat)
            if hi - u == k:  # shared attention block after each full group
                def shared_body(sp, xx):
                    return _block_apply(sp, xx, cfg, dist, positions)

                blk = _remat(shared_body) if remat else shared_body
                x, a = blk(shared, x)
                aux = aux + a
    else:
        raise ValueError(cfg.family)
    return x, aux


def forward(
    params: Params,
    batch: dict,
    cfg: ModelConfig,
    dist: L.Dist = L.LOCAL,
    *,
    remat: bool = True,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Full-sequence forward.  batch: {"tokens": (B, S), ...} -> (logits, aux)."""
    x, aux = forward_hidden(params, batch, cfg, dist, remat=remat)
    logits = _unembed(params, x, cfg, dist)
    return logits, aux


def loss_fn(
    params: Params,
    batch: dict,
    cfg: ModelConfig,
    dist: L.Dist = L.LOCAL,
    *,
    remat: bool = True,
) -> tuple[jnp.ndarray, dict]:
    """Next-token cross entropy (+ MoE aux)."""
    logits, aux = forward(params, batch, cfg, dist, remat=remat)
    tokens = batch["tokens"]
    tgt = tokens[:, 1:]
    lg = logits[:, :-1].astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, tgt[..., None], axis=-1)[..., 0]
    ce = lse - gold
    mask = jnp.ones_like(ce)
    if cfg.vision_tokens:  # do not score the image-stub positions
        mask = mask.at[:, : cfg.vision_tokens].set(0.0)
    loss = jnp.sum(ce * mask) / jnp.sum(mask)
    if cfg.moe is not None:
        loss = loss + cfg.moe.aux_loss_weight * aux / max(cfg.n_layers, 1)
    return loss, {"ce": loss, "aux": aux}


# --------------------------------------------------------------------------
# decode (serve_step)
# --------------------------------------------------------------------------


def init_decode_state(cfg: ModelConfig, batch: int, max_t: int) -> Params:
    if cfg.family in ("dense", "moe", "vlm"):
        mk = lambda _: L.attn_cache_init(cfg, batch, max_t)  # noqa: E731
        return {"layers": jax.vmap(mk)(jnp.arange(cfg.n_layers))}
    if cfg.family == "ssm":
        mk = lambda _: L.mamba_cache_init(cfg, batch)  # noqa: E731
        return {"layers": jax.vmap(mk)(jnp.arange(cfg.n_layers))}
    if cfg.family == "hybrid":
        mk = lambda _: L.mamba_cache_init(cfg, batch)  # noqa: E731
        n_units = cfg.n_layers // cfg.hybrid_attn_every
        mka = lambda _: L.attn_cache_init(cfg, batch, max_t)  # noqa: E731
        return {
            "layers": jax.vmap(mk)(jnp.arange(cfg.n_layers)),
            "shared": jax.vmap(mka)(jnp.arange(n_units)),
        }
    raise ValueError(cfg.family)


def decode_step(
    params: Params,
    tokens: jnp.ndarray,  # (B, 1) int32
    state: Params,
    pos: jnp.ndarray,  # () int32 — current position
    cfg: ModelConfig,
    dist: L.Dist = L.LOCAL,
) -> tuple[jnp.ndarray, Params]:
    """One token for every sequence in the batch; returns (logits, state)."""
    x = params["embed"][tokens].astype(L.COMPUTE_DTYPE)
    x = L._constrain(x, dist, P(dist.data_axes, None, None))
    new_state: Params = {}

    if cfg.family in ("dense", "moe", "vlm"):

        def body(carry, inp):
            lp, cache = inp
            h, a = _decode_block(lp, carry, cache, pos, cfg, dist)
            return h, a

        x, caches = scan_util.scan(body, x, (params["layers"], state["layers"]))
        new_state["layers"] = caches
    elif cfg.family == "ssm":

        def body(carry, inp):
            lp, cache = inp
            z = L.rms_norm(carry, lp["ln"], cfg.norm_eps)
            h, c = L.mamba_decode(lp["mamba"], z, cache, cfg, dist)
            return carry + h, c

        x, caches = scan_util.scan(body, x, (params["layers"], state["layers"]))
        new_state["layers"] = caches
    elif cfg.family == "hybrid":
        k = cfg.hybrid_attn_every
        n = cfg.n_layers
        shared = params["shared_block"]
        mamba_caches = []
        attn_caches = []
        for ui, u in enumerate(range(0, n, k)):
            hi = min(u + k, n)
            sub = jax.tree.map(lambda a: a[u:hi], params["layers"])
            subc = jax.tree.map(lambda a: a[u:hi], state["layers"])

            def body(carry, inp):
                lp, cache = inp
                z = L.rms_norm(carry, lp["ln"], cfg.norm_eps)
                h, c = L.mamba_decode(lp["mamba"], z, cache, cfg, dist)
                return carry + h, c

            x, mc = scan_util.scan(body, x, (sub, subc))
            mamba_caches.append(mc)
            if hi - u == k:
                ac = jax.tree.map(lambda a: a[ui], state["shared"])
                x, nc = _decode_block(shared, x, ac, pos, cfg, dist)
                attn_caches.append(nc)
        new_state["layers"] = jax.tree.map(
            lambda *xs: jnp.concatenate(xs, axis=0), *mamba_caches
        )
        # n_layers < hybrid_attn_every => no full group, shared attn unused
        new_state["shared"] = (
            jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *attn_caches)
            if attn_caches else state["shared"])
    else:
        raise ValueError(cfg.family)

    logits = _unembed(params, x, cfg, dist)
    return logits, new_state


def _decode_block(bp, x, cache, pos, cfg, dist):
    h, nc = L.attn_decode(bp["attn"], L.rms_norm(x, bp["ln1"], cfg.norm_eps),
                          cache, pos, cfg, dist)
    x = x + h
    z = L.rms_norm(x, bp["ln2"], cfg.norm_eps)
    if cfg.moe is not None and "moe" in bp:
        f, _ = L.moe_apply(bp["moe"], z, cfg, dist)
    else:
        f = L.mlp_apply(bp["mlp"], z, cfg)
    return x + f, nc


def prefill(
    params: Params,
    batch: dict,
    cfg: ModelConfig,
    dist: L.Dist = L.LOCAL,
) -> jnp.ndarray:
    """Inference prefill: returns next-token logits for the last position
    only (materializing (B, S, V) logits at 32k prefill would be ~100s of
    TB — serving only ever needs the sampling position)."""
    x, _ = forward_hidden(params, batch, cfg, dist, remat=False)
    return _unembed(params, x[:, -1:], cfg, dist)[:, 0]


# --------------------------------------------------------------------------
# paged serving path (repro.serve): packed-QTensor KV pages, flash kernels
# --------------------------------------------------------------------------

# families the paged serving path covers — the single source of truth the
# launch driver routes on (ssm/hybrid/encdec keep the legacy static batch)
PAGED_FAMILIES = ("dense", "moe", "vlm")


def _check_paged(cfg: ModelConfig) -> None:
    if cfg.family not in PAGED_FAMILIES:
        raise ValueError(
            f"paged serving covers uniform attention stacks {PAGED_FAMILIES}"
            f"; family {cfg.family!r} keeps the legacy decode path (SSM "
            "state is O(1) per sequence — paging buys nothing there)")


def _check_shardable(cfg: ModelConfig, dist: L.Dist) -> None:
    if dist.shard_axis is not None and cfg.moe is not None:
        raise NotImplementedError(
            "tensor-parallel paged serving covers dense attention stacks; "
            "moe_apply is expert-parallel (its own shard_map) and cannot "
            "nest inside the serve shard_map")


def paged_collective_bytes(cfg: ModelConfig, dist: L.Dist, *, rows: int,
                           scale_rows: int, logit_rows: int) -> dict:
    """Bytes the collectives of one tensor-parallel paged call leave on each
    shard (the size of each collective's result there), from shapes, by
    kind: ``carry`` (the psum'd attention-carry merge: pmax of m, psum of
    o and l over full heads), ``gather`` (the tiled all_gathers after
    ``wo``, the gate/up product and ``w_down``), ``scale`` (the two pmaxes
    of the K/V page amaxes) and ``logits`` (the head's vocab gather, or
    the int8 wire's int32 psum and its scale pmax).  ``rows`` are the
    query rows through the layers (the padded decode batch, or the padded
    slab), ``scale_rows`` the amaxes each page-scale pmax carries (one a
    decode row, one a slab page) and ``logit_rows`` the rows unembedded.
    All zeros on one device."""
    if dist.shard_axis is None:
        return {"carry": 0, "gather": 0, "scale": 0, "logits": 0}
    f32, act = 4, jnp.dtype(L.COMPUTE_DTYPE).itemsize
    n = cfg.n_layers
    carry = rows * cfg.n_heads * (cfg.head_dim + 2) * f32
    gather = rows * (2 * cfg.d_model + cfg.d_ff) * act
    if dist.logit_wire == "int8":
        head = logit_rows * cfg.vocab_size * 4 + f32 if logit_rows else 0
    else:
        head = 0 if cfg.tie_embeddings else logit_rows * cfg.vocab_size * act
    return {"carry": n * carry, "gather": n * gather,
            "scale": n * 2 * scale_rows * f32, "logits": head}


def init_paged_state(cfg: ModelConfig, *, n_pages: int, page_size: int,
                     kv_fmt=None) -> dict:
    """Deprecated: use ``models.api.paged_init_state`` (family-agnostic)."""
    from repro.models.api import paged_init_state  # late: api imports lm

    _check_paged(cfg)
    return paged_init_state(cfg, n_pages=n_pages, page_size=page_size,
                            kv_fmt=kv_fmt)


def _paged_stack(params: Params, x: jnp.ndarray, kv_state: dict,
                 cfg: ModelConfig, dist: L.Dist, attend):
    """The layer stack of the paged entries.  The arena rides the layer
    scan's carry whole, and the scan hands each layer its index:
    ``attend(p_attn, h, kv, layer)`` writes the layer's new K/V into the
    arena at ``[layer, page]`` and its kernel reads that layer in place, so
    no layer's slab is sliced out of the arena or stacked back into a new
    one.  Under a jit that donates the arena, a call updates it in place.
    Returns the final hidden states and the arena."""

    def body(carry, inp):
        (x, kv), (lp, layer) = carry, inp
        h, kv = attend(lp["attn"], L.rms_norm(x, lp["ln1"], cfg.norm_eps),
                       kv, layer)
        x = x + h
        z = L.rms_norm(x, lp["ln2"], cfg.norm_eps)
        if cfg.moe is not None and "moe" in lp:
            f, _ = L.moe_apply(lp["moe"], z, cfg, dist)
        else:
            f = L.mlp_apply(lp["mlp"], z, cfg, dist)
        return (x + f, kv), None

    layers = jnp.arange(kv_state["k"].shape[0], dtype=jnp.int32)
    (x, kv_state), _ = scan_util.scan(body, (x, kv_state),
                                      (params["layers"], layers))
    return x, kv_state


def paged_decode(
    params: Params,
    tokens: jnp.ndarray,   # (B, 1) int32
    kv_state: dict,        # arena pytree, leading layer axis
    page_table: jnp.ndarray,  # (B, max_pages) int32
    positions: jnp.ndarray,   # (B,) int32 — per-sequence write positions
    seq_lens: jnp.ndarray,    # (B,) int32 — 0 for padded rows
    cfg: ModelConfig,
    dist: L.Dist = L.LOCAL,
    *,
    kv_fmt,
    acc: tuple[int, int],
    oracle: bool = False,
) -> tuple[jnp.ndarray, dict]:
    """One continuous-batching decode token per sequence through the paged
    cache + flash-decode kernel.  Unlike ``decode_step``, every sequence
    carries its OWN position (the whole point of continuous batching);
    ``acc`` is the planner's carry format for the batch's context bucket.
    ``oracle=True`` routes attention through the unfused jnp reference —
    the logit-exactness oracle of the acceptance gate."""
    _check_paged(cfg)
    _check_shardable(cfg, dist)
    x = params["embed"][tokens].astype(L.COMPUTE_DTYPE)
    x = L._constrain(x, dist, P(dist.data_axes, None, None))

    def attend(p, h, kv, layer):
        return L.attn_decode_paged(
            p, h, kv, page_table, positions, seq_lens, cfg, dist,
            layer=layer, kv_fmt=kv_fmt, acc=acc, oracle=oracle)

    x, new_kv = _paged_stack(params, x, kv_state, cfg, dist, attend)
    logits = _unembed(params, x, cfg, dist)
    return logits, new_kv


def paged_verify(
    params: Params,
    tokens: jnp.ndarray,   # (B, S) int32 — last committed + k draft tokens
    kv_state: dict,        # arena pytree, leading layer axis
    page_table: jnp.ndarray,  # (B, max_pages) int32
    positions: jnp.ndarray,   # (B,) int32 — FIRST write position per row
    seq_lens: jnp.ndarray,    # (B,) int32 — attended len at slab index 0
    cfg: ModelConfig,
    dist: L.Dist = L.LOCAL,
    *,
    kv_fmt,
    acc: tuple[int, int],
    oracle: bool = False,
) -> tuple[jnp.ndarray, dict]:
    """Speculative-decode verify: score ``S = k + 1`` candidate positions
    per sequence in one batched pass, bitwise identical to ``S``
    sequential ``paged_decode`` steps over the same arena (each layer
    appends the slab's K/V under the decode path's per-slot scale
    discipline, then attends every slab index as its own decode row —
    ``layers.attn_verify_paged``).  Returns logits (B, S, V) — row ``j``
    is the model's next-token distribution AFTER consuming ``tokens[:,
    :j+1]`` — plus the post-append arena, whose rejected tail the engine
    rolls back page-exactly (``serve.kvcache.truncate_pages``)."""
    _check_paged(cfg)
    _check_shardable(cfg, dist)
    x = params["embed"][tokens].astype(L.COMPUTE_DTYPE)
    x = L._constrain(x, dist, P(dist.data_axes, None, None))

    def attend(p, h, kv, layer):
        return L.attn_verify_paged(
            p, h, kv, page_table, positions, seq_lens, cfg, dist,
            layer=layer, kv_fmt=kv_fmt, acc=acc, oracle=oracle)

    x, new_kv = _paged_stack(params, x, kv_state, cfg, dist, attend)
    logits = _unembed(params, x, cfg, dist)
    return logits, new_kv


def paged_prefill(
    params: Params,
    tokens: jnp.ndarray,         # (1, T) int32 — slab, padded to T
    kv_state: dict,
    page_row: jnp.ndarray,       # (max_pages,) int32 — full row, padded
    slab_page_ids: jnp.ndarray,  # (n_slab,) int32 — this slab's pages
    q_offset,                    # traced int32 — absolute slab start
    q_len,                       # traced int32 — live rows in the slab
    cfg: ModelConfig,
    dist: L.Dist = L.LOCAL,
    *,
    kv_fmt,
    acc: tuple[int, int],
    block_q: int | None = None,
    call=None,
    want_logits: bool = True,
) -> tuple[jnp.ndarray | None, dict]:
    """THE paged prefill: one bucket-shaped slab of one sequence through
    the stack, each layer quantizing the slab's K/V into its pages and
    attending history + slab in a single ``flash_prefill_paged`` pass over
    the post-write arena (``layers.attn_prefill_bucketed``).

    Geometry is traced: ``q_offset``/``q_len`` are int32 operands, the
    page row is padded to the bucket width, padding rows/pages are
    byte-neutral (zeros into the reserved null page).  One compiled
    instance therefore serves every slab — first, middle, ragged last,
    one-shot (``q_offset=0``), post-preemption restore — of every prompt
    in the bucket.  Walking a prompt slab-by-slab is bit-identical to one
    whole-prompt call: same arena bytes, same logits (pinned by
    ``tests/test_serve.py``).  ``want_logits`` unembeds the row at
    ``q_len - 1`` (the last live row) only on the final slab.

    Returns (logits (1, V) or None, new arena)."""
    _check_paged(cfg)
    _check_shardable(cfg, dist)
    b, t = tokens.shape
    if b != 1:
        raise ValueError("prefill is per admitted sequence (B = 1)")
    q_len = jnp.asarray(q_len, jnp.int32)
    x = params["embed"][tokens].astype(L.COMPUTE_DTYPE)
    x = L._constrain(x, dist, P(dist.data_axes, None, None))

    def attend(p, h, kv, layer):
        return L.attn_prefill_bucketed(
            p, h, kv, page_row, slab_page_ids, q_offset, q_len, cfg, dist,
            layer=layer, kv_fmt=kv_fmt, acc=acc, block_q=block_q, call=call)

    x, new_kv = _paged_stack(params, x, kv_state, cfg, dist, attend)
    if not want_logits:
        return None, new_kv
    last = jax.lax.dynamic_slice_in_dim(
        x, jnp.maximum(q_len - 1, 0), 1, axis=1)
    logits = _unembed(params, last, cfg, dist)[:, 0]
    return logits, new_kv


# The PR-6 deprecation shims (decode_step_paged, prefill_paged,
# prefill_chunk_paged here; encdec.decode_step_paged) were retired at
# their PAGED_SHIMS_SUNSET version 0.2: callers drive lm.paged_decode /
# lm.paged_prefill or the repro.models.api paged protocol.
