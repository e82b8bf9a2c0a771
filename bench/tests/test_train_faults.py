"""A whole train-16e run at a CPU-sized configuration, past the harness's
look for a chip, with the timed path sound and then broken underneath:
`correct` must be true for the sound program and false for each fault a
one-chip training cell can have (a step that returns its state unchanged;
half of the batch left out, the mean taken over the rest)."""
import time

import jax
import pytest

import run
from repro.training import loop


def _tiny_spec():
    spec = run.load_spec("train-16e")
    spec.config = dict(spec.config, n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                       head_dim=16, moe_d_ff=96, vocab_size=512, n_experts=4, top_k=2,
                       max_seq_len=256, attn_chunk=64)
    spec.traffic = dict(spec.traffic, seq_len=256, ring=4)
    # limits for this size, from CPU readings of the sound program (loss
    # <= 2.2e-5, gradient <= 1.4e-3, change <= 2.2e-3) and of the faults
    # (half batch: >= 8e-4, 0.63, 0.03; unchanged state: 1)
    spec.limits = {"loss_gap": 1e-4, "grad_norm_gap": 5e-2, "change_norm_gap": 1e-2}
    return spec


def _run(seed=5):
    run.setup_jax()
    ctx = run.Context(_tiny_spec(), seed, 1.0, False, jax.devices(), time.perf_counter())
    return run.run_cell(ctx)


def _unchanged(real):
    def make(*args, **kw):
        step = real(*args, **kw)

        def broken(state, batch):
            _, mets = step(state, batch)
            return jax.tree.map(lambda x: x + 0, state), mets

        return broken
    return make


def _half_batch(real):
    def make(model, opt_cfg, lr_fn, *, microbatches=1, **kw):
        step = real(model, opt_cfg, lr_fn, microbatches=max(microbatches // 2, 1), **kw)

        def broken(state, batch):
            return step(state, jax.tree.map(lambda x: x[: x.shape[0] // 2], batch))

        return broken
    return make


def test_sound_run_is_correct():
    res = _run()
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", [_unchanged, _half_batch])
def test_fault_is_not_correct(monkeypatch, fault):
    monkeypatch.setattr(loop, "make_train_step", fault(loop.make_train_step))
    res = _run()
    assert not res["correct"], res["checks"]
