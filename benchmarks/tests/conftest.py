"""Tests of the benchmark itself: ``python -m pytest benchmarks/tests``.

They run on the CPU (virtual devices for the mesh cell) and say nothing
about speed. Only ``test_compile_v5e.py`` loads the TPU's compiler, from
inside a fixture, in the test's own process.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=4").strip()

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
