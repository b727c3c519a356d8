"""Shared by the tests that drive the command: the cells cut to a size the
CPU holds, roomy limits, and the two things only a chip run has (the
persistent cache's directory, the device's memory statistics) stubbed."""

from __future__ import annotations

import pytest

from tests.yardstick import tiny
from yardstick import compare, harness


@pytest.fixture()
def tiny_cells(monkeypatch):
    monkeypatch.setattr(harness, "load_cell",
                        lambda name, *a, **k: tiny.cell(name))
    monkeypatch.setattr(compare, "load_limits", lambda name: tiny.LIMITS[name])
    # the persistent cache is the command's business; a test leaves none
    monkeypatch.setattr(harness, "setup_compile_cache", lambda: "off")
    # the CPU backend reports no memory statistics
    monkeypatch.setattr(harness, "memory_peak_bytes", lambda devices: 1)
