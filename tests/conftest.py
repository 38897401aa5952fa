"""Test configuration: force an 8-device CPU mesh so sharding/collective tests
run deterministically without TPU hardware (SURVEY.md §4 fake-backend testing
strategy — XLA's host platform is the fake_cpu_device.h equivalent).

Set PADDLE_TPU_TEST_ON_TPU=1 to run the suite on the real chip instead.
"""
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)
# the benchmark's own tests (no package): tests/test_benchmark_*.py import
# their cases by module name, so `pytest tests/` runs what judges every PR
sys.path.append(os.path.join(_ROOT, "benchmark", "tests"))

if not os.environ.get("PADDLE_TPU_TEST_ON_TPU"):
    from _cpu_mesh import force_host_cpu_devices

    # also exports JAX_PLATFORMS=cpu, which every subprocess the tests
    # spawn (launch children, worker scripts) inherits
    force_host_cpu_devices(8)
