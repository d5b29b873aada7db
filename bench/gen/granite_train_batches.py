"""Training batches for a Granite configuration: the draws of
``train_batches`` (uniform tokens, a pure function of seed and step), driven
by ``bench/train_granite.py``."""

from bench.gen.train_batches import batch_fn  # noqa: F401

MODE = "train_granite"
