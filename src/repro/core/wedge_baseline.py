"""The prior-art baseline the paper compares against (§V-B): open-wedge
generation + closing-edge queries (Cohen map-reduce style, as used by most
distributed TC systems before this paper).

Two paths:

  * ``wedge_triangle_count``           — single-device vectorized oracle
    (every triangle closed at each of its 3 apexes -> T = closed / 3);
  * ``parallel_wedge_triangle_count``  — shard_map implementation in which
    each device generates the wedges of its owned vertices and ROUTES EVERY
    WEDGE QUERY (v1, v2) to the owner of v1 (fixed owner-bound splitters
    through the same ``repartition_by_value`` collective) — this is the
    O(#wedges) communication pattern whose volume Table I's "Previous"
    column charges, measured here rather than assumed.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.intersect import edge_exists
from repro.core.sampling import repartition_by_value
from repro.graph.csr import Graph
from repro.graph.partition import shard_edges, vertex_partition


def wedge_count(g: Graph) -> jnp.ndarray:
    """#wedges = sum_v C(d(v), 2) — the Table I 'Wedges' column."""
    d = g.deg.astype(jnp.float64 if jax.config.jax_enable_x64 else jnp.float32)
    return jnp.sum(d * (d - 1) / 2)


@functools.partial(jax.jit, static_argnames=("d_max",))
def wedge_triangle_count(g: Graph, *, d_max: int) -> jnp.ndarray:
    """Oracle: for every directed edge (v, u) and neighbor x = N(v)[j] with
    u < x, check the closing edge (u, x)."""
    n = g.n_nodes
    starts = g.row_offsets[jnp.clip(g.src, 0, n)]
    pos = jnp.arange(d_max, dtype=jnp.int32)
    deg_ext = jnp.concatenate([g.deg, jnp.zeros((1,), jnp.int32)])
    dv = deg_ext[jnp.clip(g.src, 0, n)]
    idx = jnp.clip(starts[:, None] + pos[None, :], 0, g.num_slots - 1)
    x = jnp.where(pos[None, :] < dv[:, None], g.dst[idx], n)
    u = g.dst[:, None]
    is_wedge = (g.src[:, None] < n) & (u < x) & (x < n)
    closed = edge_exists(
        g, jnp.where(is_wedge, u, n).reshape(-1), jnp.where(is_wedge, x, n).reshape(-1)
    )
    return jnp.sum(closed, dtype=jnp.int32) // 3


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class WedgeTCResult:
    triangles: jnp.ndarray
    wedges_routed: jnp.ndarray  # measured wedge-query traffic (count)
    overflow: jnp.ndarray


def _wedge_shard(src_i, dst_i, bounds, *, n, p, d_pad, cap_chunk, axis_name):
    inf = n + 1
    valid = (src_i < n) & (dst_i < n)
    # local CSR of the shard: (src_i, dst_i) is already (src, dst)-sorted
    starts = jnp.searchsorted(src_i, jnp.arange(n + 1)).astype(jnp.int32)
    deg_local = starts[1:] - starts[:-1]  # per-vertex local degree (owners only)
    pos = jnp.arange(d_pad, dtype=jnp.int32)
    dv = deg_local[jnp.clip(src_i, 0, n - 1)]
    st = starts[jnp.clip(src_i, 0, n - 1)]
    idx = jnp.clip(st[:, None] + pos[None, :], 0, src_i.shape[0] - 1)
    x = jnp.where(pos[None, :] < dv[:, None], dst_i[idx], n)
    u = dst_i[:, None]
    is_wedge = valid[:, None] & (u < x) & (x < n)
    qu = jnp.where(is_wedge, u, inf).reshape(-1)
    qx = jnp.where(is_wedge, x, inf).reshape(-1)
    wedges_local = jnp.sum(is_wedge, dtype=jnp.int32)
    # route query (u, x) to owner(u): fixed owner-bound splitters
    rep = repartition_by_value(
        values=qu,
        carry=qx,
        valid=is_wedge.reshape(-1),
        p=p,
        cap_chunk=cap_chunk,
        axis_name=axis_name,
        inf=inf,
        splitters=bounds,
    )
    # closing-edge check against the local (src, dst)-sorted shard
    Ru, Rx = rep.values, rep.carry
    L = src_i.shape[0]
    steps = max(1, math.ceil(math.log2(L + 1)))
    lo = jnp.zeros_like(Ru)
    hi = jnp.full_like(Ru, L)
    for _ in range(steps):
        cont = lo < hi
        mid = (lo + hi) // 2
        ms = jnp.clip(mid, 0, L - 1)
        ka, kb = src_i[ms], dst_i[ms]
        less = ((ka < Ru) | ((ka == Ru) & (kb < Rx))) & cont
        lo = jnp.where(less, mid + 1, lo)
        hi = jnp.where(cont & ~less, mid, hi)
    ls = jnp.clip(lo, 0, L - 1)
    closed = (lo < L) & (src_i[ls] == Ru) & (dst_i[ls] == Rx) & (Ru < n)
    t = jax.lax.psum(jnp.sum(closed, dtype=jnp.int32), axis_name) // 3
    wedges = jax.lax.psum(wedges_local, axis_name)
    return WedgeTCResult(
        triangles=t, wedges_routed=wedges, overflow=rep.overflow
    )


def parallel_wedge_triangle_count(
    g: Graph, mesh: Mesh, *, axis_name: str = "p", slack: float = 32.0,
    d_pad: int | None = None,
) -> WedgeTCResult:
    """Note the fat default ``slack``: wedge traffic concentrates on hub
    owners (the 'curse of the last reducer', Suri et al.), so per-bucket
    chunks are far more skewed than the cover-edge transpose — memory
    pressure that is itself part of the paper's argument.  On overflow the
    result flags it; rerun with higher slack."""
    p = mesh.shape[axis_name]
    m2 = int(jax.device_get(g.n_edges_dir))
    cap_edges = max(1, math.ceil(m2 / p * 2))
    s_sh, d_sh, _, bounds = shard_edges(g, p, capacity=cap_edges)
    if d_pad is None:
        from repro.graph.csr import max_degree

        d_pad = max(1, max_degree(g))
    # wedge traffic is Σ d(v)^2-ish; per-(sender, bucket) chunk budget
    est_wedges = float(jax.device_get(wedge_count(g)))
    cap_chunk = max(8, math.ceil(slack * max(est_wedges, 1) / (p * p)))
    fn = functools.partial(
        _wedge_shard, n=g.n_nodes, p=p, d_pad=d_pad, cap_chunk=cap_chunk,
        axis_name=axis_name,
    )
    shard = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(P(axis_name), P(axis_name), P()),
        out_specs=WedgeTCResult(triangles=P(), wedges_routed=P(), overflow=P()),
    )
    sharding = NamedSharding(mesh, P(axis_name))
    s_dev = jax.device_put(jnp.asarray(s_sh.reshape(-1)), sharding)
    d_dev = jax.device_put(jnp.asarray(d_sh.reshape(-1)), sharding)
    # owner bounds as splitters: owner i gets values in (b[i-1], b[i]] — use
    # bounds[1:p] - 1 offset so that value v goes to the i with
    # bounds[i] <= v < bounds[i+1]
    spl = jnp.asarray(bounds[1:p], dtype=jnp.int32) - 1
    spl_dev = jax.device_put(spl, NamedSharding(mesh, P()))
    return jax.jit(shard)(s_dev, d_dev, spl_dev)
