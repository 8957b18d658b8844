"""Unit backfill for ``repro.graph.segment`` — the sentinel-drop
convention the per-vertex credit scatter depends on."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.graph.segment import segment_sum


# ------------------------------------------------------------- segment_sum
def test_segment_sum_basic_grouping():
    out = segment_sum(
        jnp.array([1.0, 2.0, 3.0, 4.0]), jnp.array([0, 0, 2, 2]), 3
    )
    np.testing.assert_array_equal(np.asarray(out), [3.0, 0.0, 7.0])


def test_segment_sum_drops_sentinel_ids():
    """ids >= num_segments (the padded-edge sentinel) contribute nothing."""
    data = jnp.array([1, 10, 100, 1000], dtype=jnp.int32)
    ids = jnp.array([0, 3, 1, 7])  # 3 and 7 are out of range for n=3
    out = segment_sum(data, ids, 3)
    np.testing.assert_array_equal(np.asarray(out), [1, 100, 0])


def test_segment_sum_drops_negative_ids():
    """Negative ids (the intersection engine's CAND_PAD = -1) are dropped
    too — this is exactly what the per-vertex credit scatter relies on."""
    data = jnp.ones(5, dtype=jnp.int32)
    ids = jnp.array([-1, 0, -1, 1, -1])
    out = segment_sum(data, ids, 2)
    np.testing.assert_array_equal(np.asarray(out), [1, 1])


def test_segment_sum_matrix_rows():
    data = jnp.arange(6.0).reshape(3, 2)
    out = segment_sum(data, jnp.array([1, 1, 0]), 2)
    np.testing.assert_array_equal(np.asarray(out), [[4.0, 5.0], [2.0, 4.0]])
