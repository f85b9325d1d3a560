"""Test fixtures.

Mirrors the reference's python/ray/tests/conftest.py patterns:
  * ray_start_shared  — one local cluster shared by a test module
  * ray_start_cluster — in-process multi-node Cluster for failure tests
                        (cluster_utils.Cluster, SURVEY §4.4.1)
  * CPU-jax twin      — JAX runs on a virtual 8-device CPU mesh so all TPU
                        sharding/collective code is testable hostless
                        (SURVEY §4.4), including resource lying for TPUs.
  * time_limit        — every test's whole protocol runs under TEST_LIMIT_S:
                        a wait that never ends fails that one test by name.
  * topo / one_chip   — a TPU v5e described, not attached, for the
                        ``test_chip_compile_*`` files (functions they share:
                        ``model_helpers.py``).
"""

import contextlib
import faulthandler
import os
import signal

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

# One limit for every test, set from the measurement (ROADMAP.md "The repo's
# tests": the slowest test takes 59 to 73 s under the driver's six workers).
# A test that needs more is a test to repair, so nothing lengthens it.
TEST_LIMIT_S = 240.0
# After the limit the test has failed and its fixtures tear down; if that (or
# a wait inside C, which no Python signal handler interrupts) outlasts the
# grace too, the process exits and xdist books the crash against the test.
GRACE_S = 30.0


@contextlib.contextmanager
def time_limit(nodeid, limit_s, grace_s, stderr_fd):
    """Bound one test: at `limit_s` every thread's stack goes to `stderr_fd`
    and the main thread raises pytest's failure naming `nodeid`; `grace_s`
    later the process dumps again and exits. Both timers are disarmed on exit."""

    def on_alarm(signum, frame):
        faulthandler.dump_traceback(file=stderr_fd, all_threads=True)
        pytest.fail(f"{nodeid} exceeded {limit_s:g} s", pytrace=False)

    previous = signal.signal(signal.SIGALRM, on_alarm)
    faulthandler.dump_traceback_later(limit_s + grace_s, exit=True, file=stderr_fd)
    signal.setitimer(signal.ITIMER_REAL, limit_s)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        faulthandler.cancel_dump_traceback_later()
        signal.signal(signal.SIGALRM, previous)


def pytest_configure(config):
    # Taken here, outside pytest's capture: the stacks must reach the log of
    # the run even when the process exits with the test's output still captured.
    config._time_limit_stderr_fd = os.dup(2)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_protocol(item, nextitem):
    # The whole protocol, not the call alone: a module fixture (a cluster's
    # start or shutdown) can hang as well as a body.
    with time_limit(item.nodeid, TEST_LIMIT_S, GRACE_S, item.config._time_limit_stderr_fd):
        return (yield)


@pytest.hookimpl(optionalhook=True)
def pytest_handlecrashitem(crashitem, report, sched):
    # xdist's loadfile puts a crashed worker's file back on its queue with the
    # test that crashed still pending, so a test ended by the backstop would
    # hang the next worker as well, and the next. It has failed: it is done.
    for scope, unit in list(getattr(sched, "workqueue", {}).items()):
        if crashitem in unit:
            unit[crashitem] = True
            if all(unit.values()):
                del sched.workqueue[scope]


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


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; the next one would warn. Off
    for the module that asks for ``topo``, and put back after it."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def topo(no_persistent_cache):
    """Four v5e chips described from the installed TPU compiler; skips where
    they cannot be. Each xdist worker's process loads the TPU's library for
    itself (the tier-1 command sets ``ALLOW_MULTIPLE_LIBTPU_LOAD=1``)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])
