"""`ops/prefix.py`: inclusive running sums as products with a triangle of
ones — `prefix_count` of a flag column, `prefix_sum` of a value column —
against numpy's int64 cumsum, at lengths around every level's boundary
(128, 128^2, 128^3 rows) and at each dtype's envelope edge."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from akka_tpu.ops import prefix, segment
from akka_tpu.ops.prefix import EXACT_BELOW, LANE, prefix_count, prefix_sum

LENGTHS = [1, 5, LANE - 1, LANE, LANE + 1, 4305, LANE ** 2 - 1, LANE ** 2,
           LANE ** 2 + 1, 1_148_585, LANE ** 3 + 7]


@pytest.mark.parametrize("m", LENGTHS)
@pytest.mark.parametrize("density", [0.0, 0.5, 1.0])
def test_prefix_count_equals_cumsum(m, density):
    flags = np.random.default_rng(m).random(m) < density
    got = np.asarray(jax.jit(prefix_count)(jnp.asarray(flags)))
    assert got.dtype == np.uint32 and got.shape == (m,)
    np.testing.assert_array_equal(got, np.cumsum(flags))


def _column(kind: str, m: int) -> np.ndarray:
    """int64 values whose running sums lie at the edge of what `kind`'s
    dtype carries exactly."""
    rng = np.random.default_rng(m)
    if kind == "f32-total-2^24-1":  # non-negative, the total at the edge
        total = EXACT_BELOW - 1
        col = np.full(m, total // m, np.int64)
        col[rng.choice(m, size=total % m, replace=False)] += 1
        assert col.sum() == total
        return col
    if kind == "f32-signed":  # the running sum crosses zero again and again
        return rng.integers(-50, 51, size=m)
    if kind == "int32-past-2^24":  # odd values: every bit of the sum counts
        col = rng.integers(-(1 << 16), (1 << 16) + 1, size=m) * 2 + 1
        keep = rng.random(m) < min(1.0, 12_000 / m)  # |total| under 2^31
        col = np.where(keep, col, 0)
        col[0] = (1 << 30) + 1  # the prefix starts far past 2^24
        assert np.abs(np.cumsum(col)).max() < (1 << 31)
        return col
    if kind == "bf16-total-2^8":  # ones, thinned until they total 2^8
        col = np.zeros(m, np.int64)
        col[rng.choice(m, size=min(m, 1 << 8), replace=False)] = 1
        return col
    raise ValueError(kind)


DTYPE_OF = {"f32-total-2^24-1": jnp.float32, "f32-signed": jnp.float32,
            "int32-past-2^24": jnp.int32, "bf16-total-2^8": jnp.bfloat16}


@pytest.mark.parametrize("m", LENGTHS)
@pytest.mark.parametrize("kind", list(DTYPE_OF))
def test_prefix_sum_equals_int64_cumsum(kind, m):
    col = _column(kind, m)
    dtype = DTYPE_OF[kind]
    got = jax.jit(prefix_sum)(jnp.asarray(col, dtype))
    assert got.dtype == dtype and got.shape == (m,)
    np.testing.assert_array_equal(np.asarray(got).astype(np.int64),
                                  np.cumsum(col))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32, jnp.bfloat16])
def test_prefix_sum_of_an_empty_column(dtype):
    got = jax.jit(prefix_sum)(jnp.zeros((0,), dtype))
    assert got.dtype == dtype and got.shape == (0,)


def test_prefix_count_is_dots_below_the_exact_range_and_a_cumsum_above(
        monkeypatch):
    flags = jax.ShapeDtypeStruct((4305,), jnp.bool_)
    text = jax.jit(prefix_count).lower(flags).as_text()
    assert "dot_general" in text and "reduce_window" not in text
    assert "cumsum" not in text
    # a column too long for f32 to count: the integer scan, whatever it costs
    monkeypatch.setattr(prefix, "EXACT_BELOW", 4096)
    text = jax.jit(lambda f: prefix_count(f)).lower(flags).as_text()
    assert "dot_general" not in text
    got = jax.jit(lambda f: prefix_count(f))(jnp.ones((5000,), jnp.bool_))
    np.testing.assert_array_equal(np.asarray(got), np.arange(1, 5001))


@pytest.mark.parametrize("dtype,dots", [(jnp.float32, True),
                                        (jnp.bfloat16, True),
                                        (jnp.int32, False)])
def test_prefix_sum_is_dots_for_floats_and_the_integer_scan_for_integers(
        dtype, dots):
    col = jax.ShapeDtypeStruct((4305,), dtype)
    text = jax.jit(prefix_sum).lower(col).as_text()
    assert ("dot_general" in text) == dots
    assert ("reduce_window" in text or "cumsum" in text) != dots


def _merge_deliver(sharding=None):
    """A merge `deliver` of f32 payloads at m = 4,305 (a 4,096 producer
    router pool's inbox), lowered for the default backend or `sharding`'s."""
    m, n = 4305, 201
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in (((m,), jnp.int32), ((m, 4), jnp.float32),
                                 ((m,), jnp.bool_))]
    return jax.jit(lambda d, p, v: segment.deliver(
        d, p, v, n, need_max=True, mode="merge")).lower(*args)


def test_merge_deliver_takes_its_running_sums_as_dots():
    """The columns' sums and the rank of the segment ends are dots, and no
    scan is left."""
    text = _merge_deliver().as_text()
    assert "dot_general" in text
    assert "reduce_window" not in text and "cumsum" not in text


@pytest.fixture(scope="module")
def v5e_chip():
    """One chip of a described v5e 2x2, nothing attached (a rehearsal
    compile: it says what the TPU's compiler makes of the dots, no time)."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_merge_deliver_compiled_for_v5e_keeps_its_dots_under_the_prefix_scope(
        v5e_chip):
    """What the scope table's row `akka.deliver.prefix` rests on: the TPU
    compiler leaves no `reduce-window` in a merge delivery of f32 payloads,
    and every dot carries the block's path (a cumsum's `reduce-window`s
    carry none)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()  # an entry compiled here could not be read back
    try:
        text = _merge_deliver(v5e_chip).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", old)
        cc.reset_cache()
    assert " reduce-window(" not in text and " sort(" in text
    dots = [re.search(r'op_name="([^"]*)"', line)
            for line in text.splitlines()
            if re.search(r" (convolution|dot)\(", line)]
    assert len(dots) >= 5  # four columns and the rank (one-row levels fold)
    assert all(d and "/akka.deliver.prefix/" in d.group(1) for d in dots)
