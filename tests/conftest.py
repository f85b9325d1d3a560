"""Test fixtures.

Mirrors the reference's python/ray/tests/conftest.py patterns:
  * ray_start_shared  — one local cluster shared by a test module
  * ray_start_cluster — in-process multi-node Cluster for failure tests
                        (cluster_utils.Cluster, SURVEY §4.4.1)
  * CPU-jax twin      — JAX runs on a virtual 8-device CPU mesh so all TPU
                        sharding/collective code is testable hostless
                        (SURVEY §4.4), including resource lying for TPUs.
"""

import os

# Pin the whole test process tree to a virtual 8-device CPU mesh (the CPU
# twin of a TPU slice, SURVEY §4.4): spawned worker processes inherit
# os.environ, so force the env vars, and tell a jax that is already
# imported through jax.config.update. Nothing else.
_flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(scope="module")
def ray_start_shared():
    """One cluster per test module; resources are lies (that's the point)."""
    import ray_tpu

    assert not ray_tpu.is_initialized(), "another module left a cluster up"
    # Plenty of (fake) CPUs: actors created across a module each hold one.
    ray_tpu.init(num_cpus=64, resources={"TPU": 8})
    yield
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_cluster():
    import ray_tpu
    from ray_tpu.cluster_utils import Cluster

    assert not ray_tpu.is_initialized()
    cluster = Cluster(initialize_head=True, head_node_args={"resources": {"CPU": 2}})
    ray_tpu.init(address=cluster.address)
    yield cluster
    ray_tpu.shutdown()
    cluster.shutdown()


@pytest.fixture(scope="session")
def cpu_mesh_devices():
    import jax

    devices = jax.devices()
    assert len(devices) >= 8, f"expected 8 virtual cpu devices, got {devices}"
    return devices
