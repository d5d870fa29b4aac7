"""Training driver: the program's jitted train step (``make_train_step``)
on batches drawn from the seed.

Set-up makes the weights on the device from the seed, builds the state
and the compiled step, and drives that same step through its first three
steps on the window's own feed; their readings are kept for the
comparison with the reference.  The window then runs steps for
``seconds`` and waits on the last one.  After the window, with the
program's state freed, the plain reference repeats the three steps and
the gaps are compared.
"""

from __future__ import annotations

import statistics
import time
from functools import partial

CHECK_STEPS = 3
TRACE_STEPS = 4


def make_feed(mix: dict, b: int, s: int, vocab: int, seed: int):
    """Step ``i``'s batch, made on the device from the seed: row ``r`` draws
    its ids from a Zipf law of exponent ``a_r`` over a seeded permutation
    of the vocabulary, so rows differ in how repetitive they are, as
    documents do.  The exponents are the quantiles of the mix's
    distribution; the seed only orders them, per step."""
    import jax
    import jax.numpy as jnp
    from traffic import jax_seed, quantiles

    expo = jnp.asarray(quantiles(mix["zipf_exponent"], b), jnp.float32)
    key = jax.random.PRNGKey(jax_seed(seed, 1))
    perm = jax.random.permutation(jax.random.fold_in(key, 2**31 - 1), vocab)
    logr = jnp.log(jnp.arange(1, vocab + 1, dtype=jnp.float32))

    @jax.jit
    def feed(i):
        k1, k2 = jax.random.split(jax.random.fold_in(key, i))
        a = jax.random.permutation(k1, expo)
        cdf = jnp.cumsum(jnp.exp(-a[:, None] * logr[None]), axis=1)
        u = jax.random.uniform(k2, (b, s)) * cdf[:, -1:]
        rank = jax.vmap(jnp.searchsorted)(cdf, u)
        return {"tokens": perm[jnp.minimum(rank, vocab - 1)].astype(
            jnp.int32)}

    return feed


def leaf_norms(tree):
    """{leaf name: norm, or (L,) norms of a layer-stacked leaf}."""
    import jax
    import jax.numpy as jnp

    out = {}
    for k, v in tree["layers"].items():
        v = v.astype(jnp.float32)
        out[f"layers.{k}"] = jnp.sqrt(jnp.sum(v * v, axis=tuple(
            range(1, v.ndim))))
    for k in ("embed", "final_norm"):
        v = tree[k].astype(jnp.float32)
        out[k] = jnp.sqrt(jnp.sum(v * v))
    return out


def flat_norms(tree: dict) -> dict:
    """{leaf[i]: norm}, one entry per layer of a stacked leaf."""
    import numpy as np

    out = {}
    for k, v in tree.items():
        for i, x in enumerate(np.atleast_1d(np.asarray(v, np.float64))):
            out[f"{k}[{i}]"] = float(x)
    return out


def nought_leaves(g_ref: dict) -> set:
    """Leaves whose reference gradient is under a thousandth of the median
    leaf's: nought to rounding (a key bias under softmax), so Adam moves
    them by round-off alone.  They are left out of both comparisons."""
    flat = flat_norms(g_ref)
    med = statistics.median(flat.values())
    return {k for k, v in flat.items() if v < 1e-3 * med}


def worst_gap(prog: dict, ref: dict, skip: set) -> tuple[float, str]:
    """Largest |norm_prog - norm_ref| over leaves (and layers) not in
    ``skip``, against the reference's norm of that leaf or of the median
    leaf, whichever is larger."""
    fp, fr = flat_norms(prog), flat_norms(ref)
    kept = {k: v for k, v in fr.items() if k not in skip}
    med = statistics.median(kept.values())
    worst, at = 0.0, ""
    for k, r in kept.items():
        gap = abs(fp[k] - r) / max(r, med)
        if not gap <= worst:  # NaN counts as the worst
            worst, at = (float("inf") if gap != gap else gap), k
    return worst, at


def run(cell, *, seed, seconds, trace_dir, process_start, log):
    import jax
    import jax.numpy as jnp
    import numpy as np

    import reference as R
    from harness import CompileWatch, memory_peak
    from programcfg import check_tree, model_config
    from traffic import jax_seed

    from repro.models.api import get_model
    from repro.train import optimizer as O
    from repro.train import loop

    cfg, mix = cell.config, cell.mix
    b, s = mix["batch"], mix["seq"]
    oc = cfg["optimizer"]
    model = get_model(model_config(cfg, seq_len=s, global_batch=b))
    # the paper trains fp8 (1,5,2) with a static loss scale: without one the
    # backward GEMMs' fp8 operands flush most gradients to zero
    tc = loop.TrainConfig(opt=O.OptConfig(**oc), use_loss_scaling=True,
                          scaler=O.LossScaleConfig(**cfg["loss_scale"]))
    wkey = jax.random.PRNGKey(jax_seed(seed, 0))
    make = jax.jit(partial(R.make_weights, cfg))
    feed = make_feed(mix, b, s, cfg["vocab_size"], seed)

    def init(key):
        params = R.to_program(R.make_weights(cfg, key))
        return {"params": params, "opt": O.init_opt_state(params),
                "scaler": O.init_scaler(tc.scaler)}

    check_tree(jax.eval_shape(model.init_params, wkey),
               jax.eval_shape(lambda k: R.to_program(make(k)), wkey))
    state = jax.jit(init)(wkey)
    step = jax.jit(loop.make_train_step(model, tc), donate_argnums=(0,))
    grad_norms = jax.jit(lambda opt: leaf_norms(R.from_program(jax.tree.map(
        lambda m: m / (1.0 - oc["beta1"]), opt["m"]))))
    change_norms = jax.jit(lambda p, key: leaf_norms(jax.tree.map(
        lambda a, z: a.astype(jnp.float32) - z, R.from_program(p),
        R.make_weights(cfg, key))))

    # the first steps, through the window's own call and feed
    losses, g_prog, d_prog = [], None, None
    for i in range(CHECK_STEPS):
        state, m = step(state, feed(i))
        losses.append(m["loss"])
        if i == 0:
            g_prog = grad_norms(state["opt"])
    d_prog = change_norms(state["params"], wkey)
    losses = [float(x) for x in losses]
    g_prog, d_prog = jax.device_get((g_prog, d_prog))
    log(f"program losses {losses}")

    # the window: at most two steps in flight
    watch = CompileWatch()
    t0 = time.perf_counter()
    setup_s = t0 - process_start
    watch.on = True
    i, n, prev = CHECK_STEPS, 0, None
    while True:
        state, m = step(state, feed(i))
        i, n = i + 1, n + 1
        if prev is not None:
            prev.block_until_ready()
        prev = m["loss"]
        if time.perf_counter() - t0 >= seconds:
            break
    prev.block_until_ready()
    window = time.perf_counter() - t0
    compiled = watch.close()
    rate = n * b * s / window
    last_loss = float(prev)
    log(f"window: {n} steps in {window:.3f} s, {rate:.1f} tokens/s, "
        f"set-up {setup_s:.2f} s, last loss {last_loss}")

    flat, counters = None, {"tokens_per_s": rate, "batch": b, "seq": s}
    if trace_dir is not None:
        from trace_reduce import flatten

        jax.profiler.start_trace(trace_dir)
        t_lo = time.perf_counter()
        for j in range(TRACE_STEPS):
            with jax.profiler.TraceAnnotation("bench.train.batch"):
                batch = feed(i + j)
            with jax.profiler.TraceAnnotation("bench.train.dispatch"):
                state, m = step(state, batch)
        with jax.profiler.TraceAnnotation("bench.train.wait"):
            m["loss"].block_until_ready()
        counters["traced_steps"] = TRACE_STEPS
        counters["traced_host_s"] = time.perf_counter() - t_lo
        jax.profiler.stop_trace()
        flat = flatten(trace_dir)

    mem = memory_peak(cell.chips)
    del state, m, prev
    ref_losses, g_ref, d_ref = reference_steps(cfg, b, wkey, feed)
    log(f"reference losses {ref_losses}")

    checks = gaps((losses, g_prog, d_prog), (ref_losses, g_ref, d_ref))
    skip = nought_leaves(g_ref)
    log(f"left out (reference gradient nought): {sorted(skip)}; worst "
        f"gradient leaf {worst_gap(g_prog, g_ref, skip)[1]}, worst change "
        f"leaf {worst_gap(d_prog, d_ref, skip)[1]}")
    finite = bool(np.isfinite(last_loss))
    return {
        "complete": finite, "attempted": n, "failed": 0 if finite else n,
        "memory_peak_bytes": mem,
        "metrics": {"train_tokens_per_s": rate, "setup_s": setup_s},
        "checks": sorted(checks.items()), "window_compiles": compiled,
        "counters": counters, "trace": flat,
    }


def reference_steps(cfg, b, wkey, feed, prec: str = "f32"):
    """The reference's first ``CHECK_STEPS`` steps from the same weights on
    the same batches, in blocks of rows: (losses, norms of the first
    clipped gradient, norms of the change after the last step)."""
    import jax
    import jax.numpy as jnp

    import reference as R

    oc = cfg["optimizer"]
    rows = cfg["reference"]["rows_per_block"]
    make = jax.jit(partial(R.make_weights, cfg))
    vg = jax.jit(jax.value_and_grad(partial(R.loss, cfg, prec=prec)))
    add = jax.jit(lambda a, c: jax.tree.map(jnp.add, a, c),
                  donate_argnums=(0,))
    norms = jax.jit(leaf_norms)
    w = make(wkey)
    opt = R.adamw_init(w)
    losses, g_norms = [], None
    for i in range(CHECK_STEPS):
        toks = feed(i)["tokens"]
        total, grads = 0.0, None
        for r in range(0, b, rows):
            share = min(rows, b - r) / b  # the block's share of the mean
            lv, g = vg(w, toks[r:r + rows])
            total += float(lv) * share
            g = jax.tree.map(lambda x: x * share, g)
            grads = g if grads is None else add(grads, g)
        losses.append(total)
        if i == 0:
            gnorm = float(jnp.sqrt(sum(jnp.sum(x * x)
                                       for x in jax.tree.leaves(grads))))
            clip = min(1.0, oc["grad_clip"] / (gnorm + 1e-12))
            g_norms = jax.device_get(norms(jax.tree.map(
                lambda x: x * clip, grads)))
        w, opt = R.adamw(w, grads, opt, oc)
        del grads
    w0 = make(wkey)
    d_norms = jax.device_get(norms(jax.tree.map(jnp.subtract, w, w0)))
    return losses, g_norms, d_norms


def gaps(prog, ref) -> dict:
    """The three numbers compared, from two sets of readings."""
    (lp, gp, dp), (lr, gr, dr) = prog, ref
    skip = nought_leaves(gr)
    return {"loss_gap": max(abs(a - r) / abs(r) for a, r in zip(lp, lr)),
            "grad_gap": worst_gap(gp, gr, skip)[0],
            "update_gap": worst_gap(dp, dr, skip)[0]}
