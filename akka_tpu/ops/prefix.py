"""An inclusive prefix count of a flag column that keeps its `named_scope`.

`jnp.cumsum` / `lax.cumsum` over a 1-D column reach the TPU as a
`reduce-window`, which the TPU compiler rewrites into two-dimensional ones
that carry no metadata: in a traced run they read `unscoped` whatever scope
they were called under (PERF.md sections 3 and 7.5). A dot keeps its path. So
the count is taken level by level as a product with a triangle of ones: the
column viewed as rows of `LANE` flags, each row's running count is
`row @ triu(ones)`; the rows' totals are scanned the same way, and each level
adds the exclusive total of the rows before. On the TPU that is matrix-unit
work over a column that is read once.

Exact: the factors are 0/1 flags or, past the first level, integers below
2^24, multiplied at `Precision.HIGHEST` and accumulated in f32, and a count
cannot pass the column's length, which `prefix_count` holds below 2^24.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

LANE = 128
EXACT_BELOW = 1 << 24  # f32 holds every integer up to here


def _inclusive_rows(x: jax.Array) -> jax.Array:
    """[r, LANE] f32 -> each row's inclusive running sum."""
    tri = jnp.triu(jnp.ones((LANE, LANE), jnp.float32))
    return jax.lax.dot(x, tri, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)


def _inclusive(x: jax.Array) -> jax.Array:
    """[m] f32 of integers -> inclusive running sum, [m] f32."""
    m = x.shape[0]
    rows = -(-m // LANE)
    within = _inclusive_rows(
        jnp.pad(x, (0, rows * LANE - m)).reshape(rows, LANE))
    if rows > 1:
        totals = within[:, -1]
        before = _inclusive(totals) - totals  # exclusive, over the rows
        within = within + before[:, None]
    return within.reshape(-1)[:m]


def prefix_count(flags: jax.Array) -> jax.Array:
    """[m] bool -> [m] uint32: how many flags are set at or before each row."""
    if flags.shape[0] >= EXACT_BELOW:
        return jax.lax.cumsum(flags.astype(jnp.uint32))
    return _inclusive(flags.astype(jnp.float32)).astype(jnp.uint32)
