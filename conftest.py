"""Every test, under ``tests/`` and ``bench/tests/``, starts with an empty
table of built programs (``repro.core.worker``), as it starts from the same
RNG state: a program another test built, before it patched the code a build
reads, is never served in its place."""
import sys

import pytest


@pytest.fixture(autouse=True)
def _fresh_program_table():
    worker = sys.modules.get("repro.core.worker")
    if worker is not None:
        worker.clear_program_cache()
