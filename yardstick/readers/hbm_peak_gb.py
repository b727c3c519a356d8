"""The result line's ``memory_peak_bytes`` in GB (1e9 bytes): the fullest
chip's peak of buffers in use plus the temporaries reserved for its programs
(``harness.memory_peak_bytes``), read after the window."""

from __future__ import annotations


def read(facts: dict):
    return facts["memory_peak_bytes"] / 1e9
