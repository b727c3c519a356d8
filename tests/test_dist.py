"""Unit contract of core.dist.reinitialize (round-12 satellite).

The resize path: shutdown + initialize at the new world size, retried
with backoff under its own env knobs (DTG_REINIT_RETRIES/_BACKOFF_S —
mirroring the first-init pair). Pinned against a fake jax.distributed so
no real coordinator is cycled inside the test process.
"""

import pytest


class _FakeDistributed:
    def __init__(self, fail_first=0):
        self.fail_first = fail_first
        self.shutdowns = 0
        self.inits = []

    def shutdown(self):
        self.shutdowns += 1

    def initialize(self, **kwargs):
        self.inits.append(kwargs)
        if len(self.inits) <= self.fail_first:
            raise RuntimeError("coordinator not up yet")


def test_reinitialize_retries_the_whole_cycle(monkeypatch):
    from distributed_tensorflow_guide_tpu.core import dist

    fake = _FakeDistributed(fail_first=2)
    monkeypatch.setattr(dist.jax, "distributed", fake)
    monkeypatch.setenv("DTG_REINIT_BACKOFF_S", "0.0")  # instant retries
    dist.reinitialize(dist.DistConfig(
        coordinator_address="localhost:1", num_processes=2, process_id=0))
    # the full cycle retried: a shutdown BEFORE every initialize attempt
    assert len(fake.inits) == 3 and fake.shutdowns == 3
    assert fake.inits[-1] == {"coordinator_address": "localhost:1",
                              "num_processes": 2, "process_id": 0}


def test_reinitialize_respects_retry_budget(monkeypatch):
    from distributed_tensorflow_guide_tpu.core import dist

    fake = _FakeDistributed(fail_first=99)
    monkeypatch.setattr(dist.jax, "distributed", fake)
    monkeypatch.setenv("DTG_REINIT_RETRIES", "2")
    monkeypatch.setenv("DTG_REINIT_BACKOFF_S", "0.0")
    with pytest.raises(RuntimeError, match="coordinator not up"):
        dist.reinitialize(dist.DistConfig(
            coordinator_address="localhost:1", num_processes=2,
            process_id=0))
    assert len(fake.inits) == 2  # the env knob bounded the attempts
    # a failed cycle must leave the flag DOWN: a caller falling back to
    # initialize() would otherwise hit its idempotent guard while the
    # runtime is actually torn down
    assert dist._initialized is False


def test_reinitialize_single_process_is_shutdown_only(monkeypatch):
    from distributed_tensorflow_guide_tpu.core import dist

    fake = _FakeDistributed()
    monkeypatch.setattr(dist.jax, "distributed", fake)
    for var in ("JAX_COORDINATOR_ADDRESS", "JAX_NUM_PROCESSES",
                "JAX_PROCESS_ID"):
        monkeypatch.delenv(var, raising=False)
    dist.reinitialize()
    assert fake.shutdowns == 1 and fake.inits == []
