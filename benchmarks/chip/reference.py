"""The plain reference: a Qwen2-style decoder in float32 ``jax.numpy`` at
HIGHEST matmul precision, with no kernels, cache or batching, and its
AdamW.  It imports nothing of the program and takes nothing the program
made: its weights come from ``make_weights`` below, which the harness
also hands to the program (``to_program``).

``prec`` names the precision both operands of every matrix product of
the projections and the output head are rounded to before multiplying
(``rounded``): ``"f32"`` is the reference itself; ``"int4"``
(symmetric, one absmax scale per tensor) is the control, the step below
the configuration's fp8 operands on the ladder int8/fp8 -> int4; and
``"e<E>m<M>"`` is a float of E exponent and M mantissa bits (round to
nearest even, subnormals, saturating), such as ``"e5m1"``, one mantissa
bit below the configuration's (1,5,2) operands.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def dims(cfg: dict) -> dict:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return {"d": d, "h": h, "kv": cfg["num_key_value_heads"],
            "dh": cfg.get("head_dim") or d // h,
            "f": cfg["intermediate_size"], "v": cfg["vocab_size"],
            "n": cfg["num_hidden_layers"]}


# --------------------------------- weights ---------------------------------


def weight_shapes(cfg: dict) -> dict:
    m = dims(cfg)
    d, h, kv, dh, f, v, n = (m[k] for k in ("d", "h", "kv", "dh", "f", "v",
                                            "n"))
    return {
        "embed": (v, d), "final_norm": (d,),
        "layers": {
            "ln1": (n, d), "ln2": (n, d),
            "wq": (n, d, h * dh), "bq": (n, h * dh),
            "wk": (n, d, kv * dh), "bk": (n, kv * dh),
            "wv": (n, d, kv * dh), "bv": (n, kv * dh),
            "wo": (n, h * dh, d),
            "w_gate": (n, d, f), "w_up": (n, d, f), "w_down": (n, f, d),
        },
    }


def make_weights(cfg: dict, key, dtype=jnp.float32) -> dict:
    """Random weights from ``key``, in ``dtype``; call under ``jax.jit``
    so they are made on the device in one program."""
    m = dims(cfg)
    shapes = weight_shapes(cfg)
    flat, tree = jax.tree.flatten(shapes, is_leaf=lambda x: isinstance(
        x, tuple))
    names = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(
                 shapes, is_leaf=lambda x: isinstance(x, tuple))[0]]
    keys = jax.random.split(key, len(flat))
    resid = 1.0 / math.sqrt(2 * m["n"])
    out = []
    for name, shape, k in zip(names, flat, keys):
        if "ln" in name or "norm" in name:
            x = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
        elif "'b" in name:
            x = 0.02 * jax.random.normal(k, shape, jnp.float32)
        else:
            fan_in = shape[-1] if name == "['embed']" else shape[-2]
            std = 1.0 / math.sqrt(fan_in)
            if "wo" in name or "w_down" in name:
                std *= resid
            x = std * jax.random.normal(k, shape, jnp.float32)
        out.append(x.astype(dtype))
    return jax.tree.unflatten(tree, out)


def to_program(w: dict) -> dict:
    """The same arrays in the program's parameter tree."""
    lw = w["layers"]
    return {
        "embed": w["embed"], "final_norm": w["final_norm"],
        "layers": {
            "ln1": lw["ln1"], "ln2": lw["ln2"],
            "attn": {k: lw[k] for k in ("wq", "wk", "wv", "wo", "bq", "bk",
                                        "bv")},
            "mlp": {k: lw[k] for k in ("w_gate", "w_up", "w_down")},
        },
    }


def from_program(p: dict) -> dict:
    """Inverse of ``to_program``."""
    lp = p["layers"]
    return {"embed": p["embed"], "final_norm": p["final_norm"],
            "layers": {"ln1": lp["ln1"], "ln2": lp["ln2"], **lp["attn"],
                       **lp["mlp"]}}


# --------------------------------- forward ---------------------------------


def int4(x):
    """Symmetric int4 with one absmax scale for the whole tensor."""
    s = jnp.max(jnp.abs(x)) / 7.0
    s = jnp.where(s > 0, s, 1.0)
    return jnp.clip(jnp.round(x / s), -8, 7) * s


def minifloat(x, e: int, m: int):
    """``x`` rounded to a float of ``e`` exponent and ``m`` mantissa bits
    (IEEE-style bias, subnormals, the top exponent kept for inf/nan),
    saturating at the largest finite value."""
    bias = 2 ** (e - 1) - 1
    top = (2.0 - 2.0 ** -m) * 2.0 ** (2 ** e - 2 - bias)
    ex = jnp.floor(jnp.log2(jnp.maximum(jnp.abs(x), 2.0 ** (1 - bias))))
    q = jnp.exp2(ex - m)
    return jnp.clip(jnp.round(x / q) * q, -top, top)


def rounded(x, prec: str):
    if prec == "f32":
        return x
    if prec == "int4":
        return int4(x)
    e, m = prec[1:].split("m")
    return minifloat(x, int(e), int(m))


def _mm(x, w, prec):
    x, w = rounded(x, prec), rounded(w, prec)
    return jnp.einsum("...k,kn->...n", x, w, precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def hidden(cfg: dict, w: dict, tokens, prec: str = "f32"):
    """Final normed hidden states (S, d) of one sequence ``tokens`` (S,)."""
    m = dims(cfg)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    h, kv, dh = m["h"], m["kv"], m["dh"]
    s = tokens.shape[0]
    pos = jnp.arange(s)
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    x = f32(w["embed"])[tokens]
    causal = pos[:, None] >= pos[None, :]

    def layer(x, lw):
        lw = jax.tree.map(f32, lw)
        a = _rms(x, lw["ln1"], eps)
        q = (_mm(a, lw["wq"], prec) + lw["bq"]).reshape(s, h, dh)
        k = (_mm(a, lw["wk"], prec) + lw["bk"]).reshape(s, kv, dh)
        v = (_mm(a, lw["wv"], prec) + lw["bv"]).reshape(s, kv, dh)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        q = q.reshape(s, kv, h // kv, dh)
        sc = jnp.einsum("skgd,tkd->kgst", q, k,
                        precision=HIGHEST) / math.sqrt(dh)
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        o = jnp.einsum("kgst,tkd->skgd", p, v, precision=HIGHEST)
        x = x + _mm(o.reshape(s, h * dh), lw["wo"], prec)
        b = _rms(x, lw["ln2"], eps)
        g = jax.nn.silu(_mm(b, lw["w_gate"], prec)) * _mm(b, lw["w_up"], prec)
        return x + _mm(g, lw["w_down"], prec), None

    # recompute each layer in the backward pass: the reference runs on the
    # chip beside nothing else, but must fit it at the timed sizes
    x, _ = jax.lax.scan(jax.checkpoint(layer), x, w["layers"])
    return _rms(x, f32(w["final_norm"]), eps)


def logits(cfg: dict, w: dict, tokens, prec: str = "f32", rows=None):
    """Logits (S, V), or at the positions ``rows`` only."""
    x = hidden(cfg, w, tokens, prec)
    if rows is not None:
        x = x[rows]
    return _mm(x, w["embed"].astype(jnp.float32).T, prec)


def loss(cfg: dict, w: dict, tokens, prec: str = "f32"):
    """Mean next-token cross entropy over a batch (B, S)."""
    def one(t):
        lg = logits(cfg, w, t, prec)[:-1]
        lse = jax.nn.logsumexp(lg, -1)
        gold = jnp.take_along_axis(lg, t[1:, None], -1)[:, 0]
        return jnp.mean(lse - gold)
    return jnp.mean(jax.vmap(one)(tokens))


# ---------------------------------- AdamW ----------------------------------


def adamw_init(w):
    z = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), w)
    return {"m": z, "v": jax.tree.map(jnp.copy, z), "step": 0}


def adamw(w, g, opt, oc: dict):
    """One AdamW step with global-norm clipping and linear warm-up followed
    by a cosine decay, as the configuration's ``optimizer`` states."""
    step = opt["step"] + 1
    if step < oc["warmup_steps"]:
        lr = oc["lr"] * step / max(oc["warmup_steps"], 1)
    else:
        frac = min(max((step - oc["warmup_steps"]) / max(
            oc["total_steps"] - oc["warmup_steps"], 1), 0.0), 1.0)
        lr = oc["lr"] * (oc["min_lr_ratio"] + (1 - oc["min_lr_ratio"]) * 0.5
                         * (1 + math.cos(math.pi * frac)))
    gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    scale = jnp.minimum(1.0, oc["grad_clip"] / (gnorm + 1e-12))
    b1, b2 = oc["beta1"], oc["beta2"]
    c1, c2 = 1 - b1 ** step, 1 - b2 ** step

    def upd(p, g, m, v):
        g = g * scale
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        u = (m / c1) / (jnp.sqrt(v / c2) + oc["eps"]) + oc["weight_decay"] * p
        return p - lr * u, m, v

    out = jax.tree.map(upd, w, g, opt["m"], opt["v"])
    pick = lambda i: jax.tree.map(  # noqa: E731
        lambda t: t[i], out, is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), {"m": pick(1), "v": pick(2), "step": step}
