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


def _toy_sizes_of_later_configurations() -> None:
    """tests/benchmark/bench_tiny.py shrinks EVERY configuration of
    BENCHMARK.json through its `TINY` tables and raises `KeyError` for one
    it does not know; that file and tests/benchmark/conftest.py belong to the
    accepted benchmark, which a later PR may add to and not edit. This file
    is loaded before any test under tests/, so a configuration or traffic mix
    a later PR adds registers its toy size here."""
    import sys
    here = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "benchmark")
    if here not in sys.path:
        sys.path.insert(0, here)
    import bench_tiny
    # 2,048 producers over 200 routees: about five messages a routee a step,
    # as the cell has, so the routees' maxima differ from step to step
    bench_tiny.TINY.setdefault("router-pool-100k", {
        "builder_args": {"n_producers": 2048, "n_routees": 200},
        "producers": 2048, "routees": 200, "inbox_rows": 200 + 1 + 2048 + 8})
    bench_tiny.TINY_TRAFFIC.setdefault("router-random", {
        "chunk_steps": 2, "warm_chunks": 1,
        "trace_after_seconds": 0.05, "trace_seconds": 0.1})
    # 512 tellers over 256 accounts with 4 slots: two commands a mailbox a
    # step and 32 a period of 16, so that no account is told more in a period
    # than it can take (three tellers an account leave a few accounts of 256
    # with a queue that grows all through a toy window of thousands of
    # steps), and one mailbox in twenty overflows at any step
    bench_tiny.TINY.setdefault("bank-accounts-128k", {
        "builder_args": {"n_tellers": 512, "n_accounts": 256,
                         "mailbox_slots": 4, "spill_capacity": 2048},
        "tellers": 512, "accounts": 256, "mailbox_slots": 4,
        "spill_capacity": 2048, "inbox_rows": 2048 + 256 + 512 + 8})
    bench_tiny.TINY_TRAFFIC.setdefault("bank-commands", {
        "chunk_steps": 4, "warm_chunks": 2,
        "trace_after_seconds": 0.05, "trace_seconds": 0.1})
    # the same toy bank over 4 of the 8 virtual devices: 8 logical shards,
    # two a chip; a chip holds 64 accounts and 128 tellers, a pair carries
    # Binomial(128, 1/4) commands a step, 32 +- 5, so 96 holds (and is half
    # a chip's rows: the provisioned path); a chip's spill carries under 30
    bench_tiny.TINY.setdefault("bank-sharded-128k", {
        "builder_args": {"n_tellers": 512, "n_accounts": 256, "n_shards": 8,
                         "mailbox_slots": 4, "spill_capacity": 256,
                         "remote_capacity_per_pair": 96},
        "tellers": 512, "accounts": 256, "logical_shards": 8,
        "mailbox_slots": 4, "spill_capacity": 256,
        "remote_capacity_per_pair": 96,
        "inbox_rows_per_chip": 256 + 4 * 96 + 8})
    bench_tiny.TINY_TRAFFIC.setdefault("bank-commands-mesh", {
        "chunk_steps": 4, "warm_chunks": 2,
        "trace_after_seconds": 0.05, "trace_seconds": 0.1})
    # Savina's bank at 1,024 accounts and as many tellers: about 0.6 tells
    # a row a step as at the cell's size, a stash of 4 or more within a
    # few steps, and a budget that retains mail at some steps
    bench_tiny.TINY.setdefault("savina-bank-transfer-128k", {
        "builder_args": {"n_accounts": 1024, "n_tellers": 1024,
                         "spill_capacity": 1024},
        "accounts": 1024, "tellers": 1024, "sources": 816,
        "spill_capacity": 1024, "messages_a_step": 590,
        "inbox_rows": 1024 + 1024 * 4 + 1024 + 8})
    bench_tiny.TINY_TRAFFIC.setdefault("bank-transfers", {
        "chunk_steps": 4, "warm_chunks": 2,
        "trace_after_seconds": 0.05, "trace_seconds": 0.1})
    # the same Savina bank over 4 of the 8 virtual devices: 512 accounts in
    # 8 logical shards, two a chip, and 512 tellers; a pair carries
    # Binomial(128, 1/4) messages at step 0, 32 +- 5, and fewer after, so
    # 96 holds. A budget of 2 binds at the toy's size as 4 binds at the
    # cell's (leftovers in the spill region at most steps): with it, every
    # control of the 16-step chunk after the window finds a step to plant
    # in, from wherever the window closed (in 30 of 30 tries at 8 steps;
    # with 4, the leftovers' control found none in 7 of 12)
    bench_tiny.TINY.setdefault("savina-bank-transfer-sharded-512k", {
        "builder_args": {"n_accounts": 512, "n_tellers": 512, "n_shards": 8,
                         "emit_width": 2, "spill_capacity": 256,
                         "remote_capacity_per_pair": 96},
        "accounts": 512, "tellers": 512, "logical_shards": 8,
        "accounts_per_shard": 64, "shards_per_chip": 2,
        "tellers_per_chip": 128, "rows_per_chip": 256, "sources": 408,
        "emit_width": 2, "spill_capacity": 256,
        "remote_capacity_per_pair": 96,
        "inbox_rows_per_chip": 256 + 4 * 96 + 8,
        "emission_rows_per_chip": 128 * 2 + 128, "messages_a_step": 280})
    bench_tiny.TINY_TRAFFIC.setdefault("bank-transfers-mesh", {
        "chunk_steps": 16, "warm_chunks": 2,
        "trace_after_seconds": 0.05, "trace_seconds": 0.1})


_toy_sizes_of_later_configurations()


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


@pytest.fixture(autouse=True)
def _bank_account_as_it_was():
    """tests/benchmark/test_benchmark_bank.py's faults replace `receive` of
    the module-level `bank_account` and never put it back (a file this one
    may not edit): undone after every test, so that no later test on the
    same worker builds a bank that folds with the fault (every bank builder
    traces that behavior)."""
    import sys
    bb = sys.modules.get("akka_tpu.models.baseline_benches")
    saved = bb.bank_account.receive if bb is not None else None
    yield
    if bb is not None:
        bb.bank_account.receive = saved


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
