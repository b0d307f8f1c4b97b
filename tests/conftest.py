import os
import sys

import pytest

# src layout import without install
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


@pytest.fixture
def small_row_blocks(monkeypatch):
    """Cut the row-serial streams into blocks of `kernel.SEGMENT_ALIGN`
    entries instead of `kernel.ROW_BLOCK`, so the blocked kernel's window
    slides over test-sized programs.  Executors built under another block
    size are dropped on both sides of the test."""
    from repro.core import executor
    from repro.kernels.sptrsv import kernel, ops

    executor._EXEC_CACHE.clear()
    monkeypatch.setattr(ops, "ROW_BLOCK", kernel.SEGMENT_ALIGN)
    yield kernel.SEGMENT_ALIGN
    executor._EXEC_CACHE.clear()
