"""Test env: force CPU backend with a virtual 8-device mesh so multi-chip
sharding tests run anywhere (SURVEY.md §4 TPU translation: multi-node tests
on a simulated mesh via xla_force_host_platform_device_count)."""

import os

# force-override: the ambient env may preset JAX_PLATFORMS to another platform
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running scale tests")
    config.addinivalue_line(
        "markers",
        "timing: wall-clock-coupled suites (lease TTLs, heartbeats, SBR "
        "stable-after). Deadlines auto-dilate with machine load "
        "(akka_tpu.testkit.dilation; override with "
        "AKKA_TPU_TEST_TIMEFACTOR). Run these WITHOUT pytest-xdist "
        "parallelism; they tolerate background load via dilation but "
        "sharing one core pool with other timing suites multiplies "
        "variance.")


def pytest_report_header(config):
    from akka_tpu.testkit.dilation import time_factor
    return (f"akka-tpu timing dilation: factor={time_factor():.2f} "
            f"(load={os.getloadavg()[0]:.1f}/{os.cpu_count()} cpus; "
            f"override: AKKA_TPU_TEST_TIMEFACTOR)")


import pytest  # noqa: E402


@pytest.fixture(scope="module", autouse=True)
def _benchmark_modules_start_with_no_compiled_programs(request):
    """The profiler lists every executable that is still alive in the
    process, and the benchmark's scope reader (benchmark/xscope.py) drops
    an instruction on which two programs of one module name disagree. A
    worker that ran a supervised BatchedSystem before tests/benchmark would
    leave such a `jit__run_impl` behind and the toy traced run would read
    `ring_deliver_share` 0: the outcome depended on which files xdist put
    on one worker. So a benchmark test module starts from empty caches."""
    if "benchmark" in request.node.path.parts:
        import gc
        import jax
        jax.clear_caches()
        gc.collect()
    yield
