"""From a configuration file to the program's ``ModelConfig``.

The file holds the published keys (``hidden_size``, ...) and a
``program`` group with the settings the program is run under.  The
program's own registry entry (``program.arch``) gives the model family and
its structural switches; every published size is taken from the file.
"""

from __future__ import annotations

import dataclasses


def model_config(cfg: dict, *, seq_len: int, global_batch: int):
    from repro.configs import get_config
    from repro.core.policy import AccumulationPolicy, plan_for_model

    prog = cfg["program"]
    base = get_config(prog["arch"])
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    mc = dataclasses.replace(
        base, n_layers=cfg["num_hidden_layers"], d_model=d, n_heads=h,
        n_kv_heads=cfg["num_key_value_heads"],
        d_head=cfg.get("head_dim") or d // h,
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=bool(cfg["tie_word_embeddings"]), attn_bias=True)
    policy = AccumulationPolicy(mode=prog["policy"], chunk=prog["chunk"],
                                rounding=prog.get("rounding", "rne"))
    return plan_for_model(mc, seq_len=seq_len, global_batch=global_batch,
                          policy=policy)


def check_tree(program_params_shape, ours) -> None:
    """The harness's weights must fill the program's parameter tree
    exactly: same leaves, same shapes."""
    import jax

    want = jax.tree.map(lambda x: tuple(x.shape), program_params_shape)
    got = jax.tree.map(lambda x: tuple(x.shape), ours)
    if want != got:
        raise ValueError(f"weights do not fit the program's tree:\n"
                         f"program {want}\nharness {got}")
