"""The benchmark's own Qwen3 reference (``reference_qwen3.py``) against the
program's plain reference (``repro.models.reference``) on a tiny
Qwen3-shaped configuration: both are plain f32 at HIGHEST, so they agree
to f32 rounding, with and without QKV bias and a tied head."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
import reference_qwen3 as RQ
from repro.models import reference as PR

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TP = harness.load_module(os.path.join(CHIP, "drivers", "serve_tp.py"),
                         "test_driver_serve_tp")
TINY = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 16, "num_key_value_heads": 4, "head_dim": 16,
        "attention_bias": False, "vocab_size": 256, "rms_norm_eps": 1e-06,
        "rope_theta": 1000000.0, "tie_word_embeddings": False,
        "program": {"arch": "qwen3-8b", "policy": "exact", "chunk": 64}}


@pytest.mark.parametrize("bias,tied", [(False, False), (True, True)])
def test_benchmark_reference_matches_program_reference(bias, tied):
    cfg = dict(TINY, attention_bias=bias, tie_word_embeddings=tied)
    mc = TP.model_config(cfg, seq_len=64, global_batch=1)
    assert mc.qk_norm and mc.attn_bias == bias and mc.tie_embeddings == tied
    w = RQ.make_weights(cfg, jax.random.PRNGKey(0))
    toks = jnp.asarray(np.random.RandomState(0).randint(0, 256, 40))
    ours = np.asarray(RQ.logits(cfg, w, toks))
    theirs = np.asarray(PR.logits(mc, RQ.to_program(w), toks))
    assert ours.shape == theirs.shape == (40, 256)
    np.testing.assert_allclose(ours, theirs, rtol=1e-5, atol=1e-5)
    # the program's tree is the program's own: same leaves, same shapes
    from programcfg import check_tree

    from repro.models.api import get_model

    check_tree(jax.eval_shape(get_model(mc).init_params,
                              jax.random.PRNGKey(0)),
               jax.eval_shape(lambda: RQ.to_program(w)))
