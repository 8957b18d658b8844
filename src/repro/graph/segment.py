"""The segment reduction the triangle engine scatters credit with.

``num_segments`` is always static.  Ids outside ``[0, num_segments)``
are dropped: the padded-edge sentinel ``num_segments`` and negative pads
such as the intersection engine's ``CAND_PAD`` contribute nothing, which
is how padded rows stay out of the per-vertex credit
(``core.intersect._chunk_credit``).
"""
from __future__ import annotations

import jax


def segment_sum(data, segment_ids, num_segments: int):
    """Sum of ``data`` rows grouped by ``segment_ids``.

    Sentinel convention (framework-wide): ids outside
    ``[0, num_segments)`` — the padded-edge/bag sentinel ``num_segments``
    and the negative pads like the intersection engine's ``CAND_PAD`` —
    are dropped by the underlying scatter and contribute nothing.  The
    triangle pipeline's per-vertex credit scatters rely on exactly this
    (``core.intersect._chunk_credit``)."""
    return jax.ops.segment_sum(data, segment_ids, num_segments=num_segments)
