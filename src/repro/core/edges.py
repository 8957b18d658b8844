"""Edge classification after BFS (paper §II): tree / strut / horizontal.

Only the horizontal bit is consumed by the counting algorithm (Lemma 1/2);
tree-vs-strut is provided for completeness/analysis.  ``k_fraction`` is the
paper's ``k`` — the fraction of undirected edges that are horizontal —
which drives both the modified-neighborhood size ``(2-k)m`` and the
communication model.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.bfs import UNVISITED


def horizontal_mask(
    src: jnp.ndarray, dst: jnp.ndarray, level: jnp.ndarray, n_nodes: int
) -> jnp.ndarray:
    """bool per (possibly padded) directed edge: endpoints on equal level."""
    valid = (src < n_nodes) & (dst < n_nodes)
    lev_ext = jnp.concatenate([level, jnp.full((1,), UNVISITED, jnp.int32)])
    ls = lev_ext[jnp.clip(src, 0, n_nodes)]
    ld = lev_ext[jnp.clip(dst, 0, n_nodes)]
    return valid & (ls == ld) & (ls != UNVISITED)


def horizontal_queries(g, level, *, order: str = "asc"):
    """Compact + degree-sort the horizontal undirected query edges.

    The counting algorithm only ever intersects horizontal undirected
    edges (k·m of the ``num_slots`` directed slots), so instead of probing
    every slot with non-horizontal rows sentinel-masked we stable-argsort
    the real queries to the front, keyed by small-endpoint degree — one
    sort buys both the compaction (probe work scales with k·m, not 2m)
    and the degree-bucket layout (each bucket is then a contiguous row
    range; see DESIGN.md §2).

    ``order`` picks the layout direction: ``"asc"`` (small degrees
    first — the historical single-graph layout) or ``"desc"`` (large
    degrees first — the batched layout: lanes of a ``GraphBatch`` align
    at row 0, and a per-row *max* over descending lane profiles is still
    descending, which is what lets one shared ``IntersectPlan`` cover
    every lane exactly; DESIGN.md §4).  This function is shape-polymorphic
    and vmaps over a ``GraphBatch.lane_view()`` unchanged.

    Returns ``(qu, qw, d_small, d_large, n_h)``: int32[num_slots] arrays
    whose first ``n_h`` rows are the horizontal queries (``qu < qw``)
    sorted by ``d_small`` in ``order``; trailing rows are sentinel (``n``)
    with ``d_small == d_large == 0``.
    """
    from repro.graph.csr import undirected_edges

    n = g.n_nodes
    horiz = horizontal_mask(g.src, g.dst, level, n)
    eu, ew, und = undirected_edges(g)
    use = und & horiz
    deg_ext = jnp.concatenate([g.deg, jnp.zeros((1,), jnp.int32)])
    du = deg_ext[jnp.clip(eu, 0, n)]
    dw = deg_ext[jnp.clip(ew, 0, n)]
    if order == "asc":
        big = jnp.int32(g.num_slots + 1)  # > any degree
        key = jnp.where(use, jnp.minimum(du, dw), big)
    elif order == "desc":
        # real queries have min-degree >= 1, so -1 ranks padding last
        key = -jnp.where(use, jnp.minimum(du, dw), -1)
    else:
        raise ValueError(f"order must be 'asc' or 'desc'; got {order!r}")
    sort = jnp.argsort(key, stable=True)
    qu = jnp.where(use, eu, n)[sort]
    qw = jnp.where(use, ew, n)[sort]
    d_small = jnp.where(use, jnp.minimum(du, dw), 0)[sort]
    d_large = jnp.where(use, jnp.maximum(du, dw), 0)[sort]
    n_h = jnp.sum(use, dtype=jnp.int32)
    return qu, qw, d_small, d_large, n_h


def degree_exceedance(src, dst, deg, *, widths=(), edges=()):
    """Host-side degree-histogram bounds for ``plan_buckets_bounded``,
    over blocks of edge slots: ``src``/``dst`` are ``[blocks, slots]``
    (the whole edge list as one block, or one row a shard).  A slot
    counts if it holds an undirected edge once (``src < dst``; sentinel
    pads have ``src == dst`` and drop out).  Returns ``(small, large)``:
    for each width ``w``, the most counted slots of any one block whose
    smaller endpoint has degree > ``w``; for each edge ``e``, the most
    whose larger endpoint has degree > ``e`` (at ``e = 0``: the block's
    undirected edges).

    The horizontal queries of *any* BFS are a subset of these edges, so
    the counts bound every block's rows no matter which root Algorithm 2
    runs from, before the BFS has happened (DESIGN.md §3).  This is the
    ONE place the planners' exceedance semantics are encoded: every bound
    counts degrees strictly above the width (a query with d_small == w
    fits a w-wide bucket, one with d_large == e a band that ends at e).
    """
    import numpy as np

    src, dst, deg = (np.asarray(x) for x in (src, dst, deg))
    und = src < dst
    if deg.shape[0]:
        du, dw = (deg[np.clip(x, 0, deg.shape[0] - 1)] for x in (src, dst))
    else:
        du = dw = np.zeros_like(src)
    mind = np.where(und, np.minimum(du, dw), 0)
    maxd = np.where(und, np.maximum(du, dw), 0)

    def most(d, t):
        return int((d > int(t)).sum(axis=-1).max(initial=0))

    return (tuple(most(mind, w) for w in widths),
            tuple(most(maxd, e) for e in edges))


def classify_edges(src, dst, level, n_nodes):
    """Return int8 class per directed edge: 0 pad/invalid/unvisited,
    1 horizontal, 2 adjacent-level (tree or strut).  (Tree-vs-strut needs
    parent pointers, which the counting algorithm never uses.)

    An edge between two UNVISITED vertices has ``ls == ld`` but is NOT
    horizontal — without the ``ls != UNVISITED`` guard (the same guard
    ``horizontal_mask`` applies) a partial BFS would classify every
    unreached component's edges as class 1."""
    valid = (src < n_nodes) & (dst < n_nodes)
    lev_ext = jnp.concatenate([level, jnp.full((1,), UNVISITED, jnp.int32)])
    ls = lev_ext[jnp.clip(src, 0, n_nodes)]
    ld = lev_ext[jnp.clip(dst, 0, n_nodes)]
    horiz = valid & (ls == ld) & (ls != UNVISITED)
    adj = valid & (ls != UNVISITED) & (ld != UNVISITED) & (
        jnp.abs(ls - ld) == 1
    )
    return jnp.where(horiz, 1, jnp.where(adj, 2, 0)).astype(jnp.int8)


def k_fraction(src, dst, level, n_nodes) -> jnp.ndarray:
    """Paper's k: |horizontal undirected edges| / m."""
    h = horizontal_mask(src, dst, level, n_nodes)
    und = src < dst  # count each undirected edge once
    m = jnp.sum((src < n_nodes) & (dst < n_nodes) & und)
    return jnp.sum(h & und) / jnp.maximum(m, 1)
