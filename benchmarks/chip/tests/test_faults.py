"""The harness's correctness check on a tiny CPU run, with the timed path
broken underneath: each fault the cells can have must come out as not
correct, and the unbroken program as correct."""

import pytest

from conftest import run_tiny


def test_unbroken_train_is_correct(root):
    r = run_tiny(root, "tiny.train")
    assert r["correct"], r["compared"]


def test_unbroken_serve_is_correct(root):
    r = run_tiny(root, "tiny.decode")
    assert r["correct"], r["compared"]


def test_step_returning_its_state_unchanged(root, monkeypatch):
    from repro.train import loop

    real = loop.make_train_step

    def unchanged(model, tc, *a, **k):
        step = real(model, tc, *a, **k)
        return lambda state, batch: (state, step(state, batch)[1])

    monkeypatch.setattr(loop, "make_train_step", unchanged)
    r = run_tiny(root, "tiny.train")
    assert not r["correct"]
    assert r["compared"]["update_gap"]["value"] > 0.9


def test_half_of_the_batch_left_out(root, monkeypatch):
    from repro.train import loop

    real = loop.make_train_step

    def half(model, tc, *a, **k):
        step = real(model, tc, *a, **k)

        def broken(state, batch):
            b = batch["tokens"].shape[0]
            return step(state, {"tokens": batch["tokens"][:b // 2]})
        return broken

    monkeypatch.setattr(loop, "make_train_step", half)
    r = run_tiny(root, "tiny.train")
    assert not r["correct"], r["compared"]


@pytest.mark.parametrize("workload", ["tiny.decode", "tiny.prefill"])
def test_token_altered_where_it_is_produced(root, monkeypatch, workload):
    from repro.serve import scheduler

    real = scheduler.ModelExecutor.decode

    def altered(self, req):
        toks = real(self, req)
        toks[0] = (toks[0] + 1) % self.cfg.vocab_size
        return toks

    monkeypatch.setattr(scheduler.ModelExecutor, "decode", altered)
    r = run_tiny(root, workload)
    assert not r["correct"], r["compared"]


def test_program_compiled_inside_the_window(root, monkeypatch):
    import jax
    import jax.numpy as jnp

    import harness
    from repro.serve import scheduler

    real = scheduler.ModelExecutor.decode
    calls = []

    def compiling(self, req):
        calls.append(1)
        # a new shape each call: a fresh program, as an eager op at a new
        # row count would be
        jax.jit(lambda x: x + 1)(jnp.zeros(len(calls) + 1000))
        return real(self, req)

    monkeypatch.setattr(scheduler.ModelExecutor, "decode", compiling)
    with pytest.raises(harness.WindowCompiled):
        run_tiny(root, "tiny.decode")
