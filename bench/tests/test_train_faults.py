"""A whole run of every training cell that keeps a CPU size
(fixtures/<cell>.cpu.json), past the harness's look for a chip, with the
timed path sound and then broken underneath: `correct` must be true for the
sound program and false for each fault a one-chip training cell can have
(a step that returns its state unchanged; half of the batch left out, the
mean taken over the rest)."""
import time

import jax
import pytest

import cells
import run
from repro.training import loop

CELLS = cells.cpu_cells("train")


def _run(cell, seed=5):
    run.setup_jax()
    ctx = run.Context(cells.cpu_spec(cell), seed, 1.0, False, jax.devices(),
                      time.perf_counter())
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


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    res = _run(cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", [_unchanged, _half_batch])
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_not_correct(monkeypatch, cell, fault):
    monkeypatch.setattr(loop, "make_train_step", fault(loop.make_train_step))
    res = _run(cell)
    assert not res["correct"], res["checks"]
