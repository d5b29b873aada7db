"""Shared tiny configurations for the benchmark's own tests.

The benchmark package lives at the repository root (``bench/``); these
tests import it from there.  Sizes are the smoke size the other CPU tests
use, so a whole run of a cell fits in seconds.
"""

import copy
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TINY = {"n_layers": 2, "d_model": 64, "n_heads": 4, "head_dim": 16,
        "d_ff": 96, "vocab_size": 509, "vocab_pad_to": 64}


def tiny_conf(name: str, **model) -> dict:
    from bench import model as bm
    conf = copy.deepcopy(bm.load_json("configs", name))
    conf["model"].update(TINY, n_kv_heads=min(conf["model"]["n_kv_heads"], 4))
    conf["model"].update(model)
    return conf


TRAIN_TRAFFIC = {"kind": "train_batches", "batch": 4, "seq": 32,
                 "chunk_seconds": 0.2, "tokens": "uniform"}


def _metric(name, unit, better, source, cells, **kw):
    return {"name": name, "unit": unit, "better": better, "source": source,
            "workloads": cells, **kw}


TRAIN = ["tx.train.bhq5"]
# the cells these tests drive, whatever BENCHMARK.json holds
TEST_BENCH = {
    "configs": [{"name": "statquant-tx"}],
    "workloads": [
        {"name": "tx.train.bhq5", "config": "statquant-tx",
         "traffic": "train.b32s512", "chips": 1}],
    "end_to_end": [
        _metric("train_tokens_per_s", "tokens/s", "higher", "host_clock",
                TRAIN),
        {"name": "setup_s", "unit": "s", "better": "lower",
         "source": "host_clock"}],
    "per_layer": [
        _metric("mfu.train", "%", "higher", "host_clock", TRAIN,
                moves="train_tokens_per_s"),
        _metric("idle_share.train", "%", "lower", "device_trace", TRAIN,
                moves="train_tokens_per_s")],
}


@pytest.fixture
def tx_tiny():
    return tiny_conf("statquant-tx")


@pytest.fixture
def swiglu_tiny():
    """The reference's other block family (SwiGLU, RMSNorm, no qkv bias,
    grouped key/value heads) on the same registry entry."""
    return tiny_conf("statquant-tx", act="swiglu", norm="rmsnorm",
                     qkv_bias=False, n_kv_heads=2)
