"""Seeded generators: one seed gives identical inputs, another different
ones."""

import numpy as np

from bench.gen import load, train_batches
from conftest import TRAIN_TRAFFIC

M = {"vocab_size": 509}


def test_train_batches_are_a_function_of_seed_and_step():
    a = train_batches.batch_fn(TRAIN_TRAFFIC, M, 7)
    b = train_batches.batch_fn(TRAIN_TRAFFIC, M, 7)
    c = train_batches.batch_fn(TRAIN_TRAFFIC, M, 8)
    x, y, z = a(3), b(3), c(3)
    np.testing.assert_array_equal(x["tokens"], y["tokens"])
    assert not np.array_equal(x["tokens"], z["tokens"])
    assert not np.array_equal(x["tokens"], a(4)["tokens"])
    np.testing.assert_array_equal(x["tokens"][:, 1:], x["labels"][:, :-1])
    assert x["tokens"].shape == (4, 32)
    rows = {tuple(r) for s in range(3) for r in np.asarray(a(s)["tokens"])}
    assert len(rows) == 12                           # every row differs
    assert load("train_batches").MODE == "train"


def test_large_seeds_are_accepted():
    big = 2 ** 31 + 12345
    x = train_batches.batch_fn(TRAIN_TRAFFIC, M, big)(0)
    y = train_batches.batch_fn(TRAIN_TRAFFIC, M, big + 2 ** 32)(0)
    assert not np.array_equal(x["tokens"], y["tokens"])
