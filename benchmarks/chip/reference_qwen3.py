"""The plain reference of the Qwen3 cells: a Qwen3 decoder (arXiv:
2505.09388) in float32 ``jax.numpy`` at HIGHEST matmul precision, with no
kernels, cache, batching or sharding code.  Beside ``reference.py``'s
Qwen2 decoder it has RMSNorm over ``head_dim`` on q and on k before RoPE
(qk-norm), QKV bias only where ``attention_bias`` says so, and an untied
``lm_head`` unless ``tie_word_embeddings``.  It imports nothing of the
program and takes nothing the program made: its weights come from
``make_weights`` below, which the harness also hands to the program
(``to_program``).

The weights are stored in the dtype the caller asks for (bf16 on the
chip) and read one layer at a time into float32 (``lax.scan``), so that
the reference of a model that fits only over several chips runs over them
too, wherever the caller places the weights: the computation itself has
no sharding code.

``prec`` names the precision both operands of every matrix product of
the projections and the output head are rounded to, as in
``reference.py`` (``rounded``): ``"f32"`` is the reference itself,
``"int4"`` the control, ``"e<E>m<M>"`` a narrow float.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from reference import HIGHEST, _mm, _rms, _rope, dims


def weight_shapes(cfg: dict) -> dict:
    m = dims(cfg)
    d, h, kv, dh, f, v, n = (m[k] for k in ("d", "h", "kv", "dh", "f", "v",
                                            "n"))
    layers = {
        "ln1": (n, d), "ln2": (n, d),
        "wq": (n, d, h * dh), "wk": (n, d, kv * dh), "wv": (n, d, kv * dh),
        "q_norm": (n, dh), "k_norm": (n, dh),
        "wo": (n, h * dh, d),
        "w_gate": (n, d, f), "w_up": (n, d, f), "w_down": (n, f, d),
    }
    if cfg.get("attention_bias"):
        layers.update(bq=(n, h * dh), bk=(n, kv * dh), bv=(n, kv * dh))
    out = {"embed": (v, d), "final_norm": (d,), "layers": layers}
    if not cfg.get("tie_word_embeddings"):
        out["lm_head"] = (d, v)
    return out


def make_weights(cfg: dict, key, dtype=jnp.float32) -> dict:
    """Random weights from ``key``, in ``dtype``: norm scales 1 + N(0,
    0.1), biases N(0, 0.02), matrices N(0, 1/fan_in) with ``wo`` and
    ``w_down`` further scaled by 1/sqrt(2 * layers).  Call under
    ``jax.jit`` so they are made on the device in one program."""
    shapes = weight_shapes(cfg)
    paths, tree = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(key, len(paths))
    resid = 1.0 / math.sqrt(2 * dims(cfg)["n"])
    out = []
    for (path, shape), k in zip(paths, keys):
        name = jax.tree_util.keystr(path[-1:])[2:-2]
        if "norm" in name or name in ("ln1", "ln2"):
            x = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
        elif name in ("bq", "bk", "bv"):
            x = 0.02 * jax.random.normal(k, shape, jnp.float32)
        else:
            fan_in = shape[-1] if name == "embed" else shape[-2]
            std = 1.0 / math.sqrt(fan_in)
            if name in ("wo", "w_down"):
                std *= resid
            x = std * jax.random.normal(k, shape, jnp.float32)
        out.append(x.astype(dtype))
    return jax.tree.unflatten(tree, out)


_ATTN = ("wq", "wk", "wv", "wo", "bq", "bk", "bv", "q_norm", "k_norm")
_MLP = ("w_gate", "w_up", "w_down")


def to_program(w: dict) -> dict:
    """The same arrays in the program's parameter tree."""
    lw = w["layers"]
    out = {k: v for k, v in w.items() if k != "layers"}
    out["layers"] = {"ln1": lw["ln1"], "ln2": lw["ln2"],
                     "attn": {k: lw[k] for k in _ATTN if k in lw},
                     "mlp": {k: lw[k] for k in _MLP}}
    return out


def from_program(p: dict) -> dict:
    """Inverse of ``to_program``."""
    lp = p["layers"]
    out = {k: v for k, v in p.items() if k != "layers"}
    out["layers"] = {"ln1": lp["ln1"], "ln2": lp["ln2"], **lp["attn"],
                     **lp["mlp"]}
    return out


def hidden(cfg: dict, w: dict, tokens, prec: str = "f32"):
    """Final normed hidden states (S, d) of one sequence ``tokens`` (S,)."""
    m = dims(cfg)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    h, kv, dh = m["h"], m["kv"], m["dh"]
    bias = bool(cfg.get("attention_bias"))
    s = tokens.shape[0]
    pos = jnp.arange(s)
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    x = f32(w["embed"])[tokens]
    causal = pos[:, None] >= pos[None, :]

    def proj(a, lw, name, heads):
        y = _mm(a, lw["w" + name], prec)
        if bias:
            y = y + lw["b" + name]
        return y.reshape(s, heads, dh)

    def layer(x, lw):
        lw = jax.tree.map(f32, lw)
        a = _rms(x, lw["ln1"], eps)
        q = _rms(proj(a, lw, "q", h), lw["q_norm"], eps)
        k = _rms(proj(a, lw, "k", kv), lw["k_norm"], eps)
        v = proj(a, lw, "v", kv)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        q = q.reshape(s, kv, h // kv, dh)
        sc = jnp.einsum("skgd,tkd->kgst", q, k,
                        precision=HIGHEST) / math.sqrt(dh)
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        o = jnp.einsum("kgst,tkd->skgd", p, v, precision=HIGHEST)
        x = x + _mm(o.reshape(s, h * dh), lw["wo"], prec)
        b = _rms(x, lw["ln2"], eps)
        g = jax.nn.silu(_mm(b, lw["w_gate"], prec)) * _mm(b, lw["w_up"],
                                                          prec)
        return x + _mm(g, lw["w_down"], prec), None

    x, _ = jax.lax.scan(layer, x, w["layers"])
    return _rms(x, f32(w["final_norm"]), eps)


def logits(cfg: dict, w: dict, tokens, prec: str = "f32", rows=None):
    """Logits (S, V), or at the positions ``rows`` only."""
    x = hidden(cfg, w, tokens, prec)
    if rows is not None:
        x = x[rows]
    head = w["embed"].T if cfg.get("tie_word_embeddings") else w["lm_head"]
    return _mm(x, head.astype(jnp.float32), prec)
