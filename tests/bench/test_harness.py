"""Whole runs of the training cell on the CPU at the smoke size, past the
harness's look for a chip: a sound run is correct, and a run with the
timed path broken underneath comes out not correct — the step that returns
its state unchanged, half the batch left out, and the program run at the
control's lower precision."""

import pytest

from bench import check, faults, run
from conftest import TEST_BENCH, TRAIN_TRAFFIC

# the cell's own limits also separate at this size: sound smoke runs read
# loss 2.5e-4-7.3e-4 and worst leaf's change 0.04-0.12 on three seeds; the
# control reads loss 7.2e-3 or more, half the batch 1.6e-2 or more, and a
# state left unchanged reads change 1
LIMITS = check.load_limits("tx.train.bhq5")


def _run(conf, **overrides):
    res = run.run_cell("tx.train.bhq5", 12345678901, 0.3, False,
                       require_chip=False, bench=TEST_BENCH,
                       overrides={"conf": conf, "traffic": TRAIN_TRAFFIC,
                                  "limits": LIMITS, **overrides})
    res.pop("_out")
    return res


def test_sound_training_run_is_correct(tx_tiny):
    res = _run(tx_tiny)
    assert res["correct"], res["checks"]
    assert [c["name"] for c in res["checks"]] == list(LIMITS)
    assert res["metrics"]["train_tokens_per_s"]["unit"] == "tokens/s"
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}


@pytest.mark.parametrize("fault", sorted(faults.TRAIN))
def test_broken_training_step_is_not_correct(tx_tiny, fault):
    res = _run(tx_tiny, step_wrap=faults.TRAIN[fault])
    assert not res["correct"], res["checks"]


def test_lower_precision_control_is_not_correct(tx_tiny):
    res = _run(tx_tiny, policy=tx_tiny["train"]["control_policy"])
    assert not res["correct"], res["checks"]
