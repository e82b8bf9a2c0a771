"""The generators: deterministic per seed, within the mixes' stated ranges."""
import json
import os

import jax
import numpy as np

import run

MIXES = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "traffic")


def _mix(name):
    with open(os.path.join(MIXES, name + ".json")) as fh:
        return json.load(fh)


def _small_train():
    mix = _mix("train_8k")
    mix.update(seq_len=256, ring=3)
    return mix


def test_seed_key_takes_large_seeds():
    a, b = run.seed_key(2 ** 31 + 5), run.seed_key(5)
    assert not np.array_equal(np.asarray(jax.random.key_data(a)),
                              np.asarray(jax.random.key_data(b)))


def test_train_ring_is_deterministic_and_in_range():
    mix = _small_train()
    gen = run.generator(mix)
    make = jax.jit(lambda k: gen.make(mix, 6400, k))
    a = np.asarray(make(run.seed_key(3_000_000_019)))
    b = np.asarray(make(run.seed_key(3_000_000_019)))
    c = np.asarray(make(run.seed_key(11)))
    assert a.shape == (3, mix["batch"], 257)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert a.min() >= 0 and a.max() < 6400
    rows = a.reshape(-1, 257)
    assert len({r.tobytes() for r in rows}) == len(rows)  # every row differs
    # Zipf skew: the most frequent token of a row takes far more than 1/vocab
    top = max(np.bincount(rows[0]).max(), 1) / rows.shape[1]
    assert top > 20 / 6400


def test_every_mix_names_a_generator():
    for name in os.listdir(MIXES):
        mix = _mix(name[:-len(".json")])
        assert hasattr(run.generator(mix), "make"), name
