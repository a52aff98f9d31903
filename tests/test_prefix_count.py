"""`ops/prefix.py::prefix_count`: the inclusive prefix count of a flag
column as products with a triangle of ones, against numpy's cumsum, at
lengths around every level's boundary (128, 128^2, 128^3 rows)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from akka_tpu.ops import prefix
from akka_tpu.ops.prefix import LANE, prefix_count


@pytest.mark.parametrize("m", [1, 5, LANE - 1, LANE, LANE + 1, 4305,
                               LANE ** 2 - 1, LANE ** 2, LANE ** 2 + 1,
                               1_148_585, LANE ** 3 + 7])
@pytest.mark.parametrize("density", [0.0, 0.5, 1.0])
def test_prefix_count_equals_cumsum(m, density):
    flags = np.random.default_rng(m).random(m) < density
    got = np.asarray(jax.jit(prefix_count)(jnp.asarray(flags)))
    assert got.dtype == np.uint32 and got.shape == (m,)
    np.testing.assert_array_equal(got, np.cumsum(flags))


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
