"""Inclusive running sums of a 1-D column that keep their `named_scope`.

`jnp.cumsum` / `lax.cumsum` over a 1-D column reach the TPU as a
`reduce-window`, which the TPU compiler rewrites into two-dimensional ones
that carry no metadata and take 0.21 ms over a million rows: in a traced run
they read `unscoped` whatever scope they were called under (PERF.md sections
3 and 6). A dot keeps its path and is matrix-unit work. So the sum is taken
level by level as a product with a triangle of ones: the column viewed as
rows of `LANE` values, each row's running sum is `row @ triu(ones)`; the
rows' totals are scanned the same way, and each level adds the exclusive
total of the rows before. The column is read once.

What is exact. The triangle's entries are exactly 0 and 1, the products are
taken at `Precision.HIGHEST` (on the TPU the f32 factor split into three
bf16 parts, each multiplied by 1.0) and accumulated in f32: the result is a
sum of the same f32 numbers in another order than a scan's. Integer-valued
columns are exact while no partial sum leaves f32's integers (2^24,
`EXACT_BELOW`); a count cannot pass the column's length, which
`prefix_count` holds below that. Every value must be finite: the triangle's
zeros turn an inf or a NaN into NaN for its whole row of `LANE`, where a scan
spoils only what follows it.
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
    """[m] f32 -> inclusive running sum, [m] f32."""
    m = x.shape[0]
    rows = -(-m // LANE)
    within = _inclusive_rows(
        jnp.pad(x, (0, rows * LANE - m)).reshape(rows, LANE))
    if rows > 1:
        totals = within[:, -1]
        before = _inclusive(totals) - totals  # exclusive, over the rows
        within = within + before[:, None]
    return within.reshape(-1)[:m]


def prefix_sum(col: jax.Array) -> jax.Array:
    """[m] -> [m] of the same dtype: the sum of the rows at or before each.

    Floats of at most 32 bits go through the dots with f32 accumulation (a
    bf16 column's sums are rounded to bf16 once, at the end). Every other
    dtype keeps the scan in its own arithmetic: an int32 column is exact to
    2^31 there and would be to 2^24 under an f32 accumulator."""
    dt = col.dtype
    if jnp.issubdtype(dt, jnp.floating) and jnp.finfo(dt).bits <= 32:
        return _inclusive(col.astype(jnp.float32)).astype(dt)
    return jnp.cumsum(col)


def prefix_count(flags: jax.Array) -> jax.Array:
    """[m] bool -> [m] uint32: how many flags are set at or before each row."""
    if flags.shape[0] >= EXACT_BELOW:
        return jax.lax.cumsum(flags.astype(jnp.uint32))
    return _inclusive(flags.astype(jnp.float32)).astype(jnp.uint32)
