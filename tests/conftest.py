"""Test harness: 8 virtual CPU devices in one process.

This is the JAX analogue of the TF in-process fake cluster
(tensorflow/python/framework/test_util.py create_local_cluster /
tensorflow/python/distribute/multi_worker_test_base.py
create_in_process_cluster): real collective semantics, no real fabric.
Env must be set before jax initializes its backends, hence module top-level.
"""

import os

# Tests always run on fake CPU devices, whatever the ambient platform.
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402
import pytest  # noqa: E402

# Must land before anything touches a backend, which is why this file is
# imported first.
jax.config.update("jax_num_cpu_devices", 8)


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 fake devices, got {len(devs)}"
    return devs


@pytest.fixture()
def mesh8():
    from distributed_tensorflow_guide_tpu.core.mesh import MeshSpec, build_mesh

    return build_mesh(MeshSpec(data=-1))


@pytest.fixture()
def rng():
    return jax.random.PRNGKey(0)


@pytest.fixture()
def isolated_autotune_table(tmp_path, monkeypatch):
    """An empty in-memory autotune table redirected to a tmp file — nothing
    leaks between tests or to the user cache. One definition (round 9) for
    the fixtures test_autotune / test_fused_ce / test_overlap all declare
    autouse wrappers around."""
    from distributed_tensorflow_guide_tpu.ops import autotune

    monkeypatch.setenv("DTG_AUTOTUNE_TABLE", str(tmp_path / "table.json"))
    autotune.reset()
    yield autotune
    autotune.reset()
