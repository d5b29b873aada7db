"""Share of the traced training step's device self time in the
full-precision attention, the ops under ``fp[attn.sdpa]``
(``bench/attribution.py``)."""

from bench import attribution


def read(run):
    return attribution.share(run, "attn")
