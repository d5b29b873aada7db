"""Seeded traffic generators.  ``traffic/<mix>.json`` names one by its
``kind``; the module's ``MODE`` names the runner that drives it
(``bench/<MODE>.py``).  The system under test receives only what a generator makes."""

import importlib


def load(kind: str):
    return importlib.import_module(f"bench.gen.{kind}")
