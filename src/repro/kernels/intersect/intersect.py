"""Pallas TPU kernel: batched sorted-list intersection with level split.

Hardware adaptation (DESIGN.md §2): the paper intersects adjacency lists
with *hash tables* — pointer-chasing probes that map terribly onto the TPU
VPU.  The TPU-native formulation is a **tiled all-pairs compare** over the
two sorted lists: each grid step loads a (BQ, BD) candidate tile and a
(BQ, BD) target tile into VMEM and evaluates the (BQ, BD, BD) equality
cube with 8x128-lane vector ops.  Sorted inputs give a cheap tile-level
early-out (`pl.when`) — whole tile pairs whose value ranges don't overlap
are skipped, recovering most of merge-path's advantage without its serial
two-pointer dependency.

Work per query is O(Dc·Dt / V) vector slots vs the paper's O(D) serial
hash probes; for V = 8*128 VPU lanes and the D <= few-hundred sublists
produced by degree bucketing, the crossover strongly favors the vector
form — and it needs no hash-table build, no scatter, no data-dependent
control flow.

The candidate and target widths are independent (``cand: (Q, Dc)``,
``targ: (Q, Dt)``): the bucketed pipeline gathers candidates from the
*smaller*-degree endpoint at the bucket width and targets from the larger
endpoint at its own (possibly hub-sized) width, so low-degree buckets
never pay hub padding on the candidate side.

Grid: (Q/BQ, Dc/BD, Dt/BD); the counter outputs are revisited across the
inner two grid dims and accumulated in place (sequential TPU grid).

Each launch is named ``intersect_<role>_w<candidate width>``
(``intersect_split_w256``, ``intersect_count_w32``, ``intersect_hits_w4096``):
the device trace then tells the kernels and the plan's buckets apart.

TPU layout: every block is 2-D.  Per-query values (the apex level
``lev_u`` in, the counters out) travel as ``(Q, 1)`` columns with
``(BQ, 1)`` blocks — the Mosaic lowering refuses rank-1 blocks narrower
than a 128-lane tile.  Outputs carry the mesh axes their inputs vary
over, so the kernels trace inside ``jax.shard_map`` with ``check_vma``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CAND_PAD = -1
TARG_PAD = -2


def default_interpret() -> bool:
    """Pallas ``interpret`` default: compiled on real TPU, interpreter
    everywhere else (CPU containers, GPU)."""
    return jax.default_backend() != "tpu"


def _resolve_interpret(interpret):
    return default_interpret() if interpret is None else bool(interpret)


def _tile_hit(cand_ref, targ_ref, body):
    """Run ``body(hit)`` on this grid step's tile pair unless their value
    ranges cannot overlap.  ``hit`` is bool[BQ, BD]: which candidates
    (sorted rows, pad -1) occur in the target row (pad -2)."""
    cand = cand_ref[...]
    targ = targ_ref[...]
    # sorted rows => ranges that don't overlap anywhere in the whole tile
    # can never match (pads are negative, real ids >= 0)
    c_lo, c_hi = jnp.min(cand), jnp.max(cand)
    t_lo, t_hi = jnp.min(targ), jnp.max(targ)
    overlap = (c_hi >= 0) & (t_hi >= 0) & (c_lo <= t_hi) & (t_lo <= c_hi)

    @pl.when(overlap)
    def _work():
        eq = cand[:, :, None] == targ[:, None, :]
        body(jnp.any(eq, axis=2) & (cand >= 0))


def _row_sum(mask):
    return jnp.sum(mask.astype(jnp.int32), axis=1, keepdims=True)


def _kernel(cand_ref, targ_ref, lev_c_ref, lev_u_ref, c1_ref, c2_ref):
    @pl.when((pl.program_id(1) == 0) & (pl.program_id(2) == 0))
    def _init():
        c1_ref[...] = jnp.zeros_like(c1_ref)
        c2_ref[...] = jnp.zeros_like(c2_ref)

    def body(hit):
        same = lev_c_ref[...] == lev_u_ref[...]  # (BQ, BD) vs (BQ, 1)
        c1_ref[...] += _row_sum(hit & ~same)
        c2_ref[...] += _row_sum(hit & same)

    _tile_hit(cand_ref, targ_ref, body)


def _count_kernel(cand_ref, targ_ref, cnt_ref):
    @pl.when((pl.program_id(1) == 0) & (pl.program_id(2) == 0))
    def _init():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    def body(hit):
        cnt_ref[...] += _row_sum(hit)

    _tile_hit(cand_ref, targ_ref, body)


def _hits_kernel(cand_ref, targ_ref, hit_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        hit_ref[...] = jnp.zeros_like(hit_ref)

    def body(hit):
        hit_ref[...] |= hit.astype(jnp.int32)

    _tile_hit(cand_ref, targ_ref, body)


def _launch(kernel, role, cand, targ, extra, out_cols, *, block_q, block_d,
            interpret):
    """Pad ``cand``/``targ`` (and the per-candidate / per-query ``extra``
    inputs) up to block multiples and run ``kernel`` over the
    (Q/BQ, Dc/BD, Dt/BD) grid as ``intersect_<role>_w<Dc>``.
    ``out_cols`` lists each int32 output's column layout: ``1`` for a
    per-query ``(Q, 1)`` counter, ``"cand"`` for a ``(Q, Dc)``
    per-candidate tile.  Returns the padded outputs."""
    q, dc = cand.shape
    dt = targ.shape[1]
    qp = -(-q // block_q) * block_q
    dcp = -(-dc // block_d) * block_d
    dtp = -(-dt // block_d) * block_d
    cand = jnp.pad(cand, ((0, qp - q), (0, dcp - dc)), constant_values=CAND_PAD)
    targ = jnp.pad(targ, ((0, qp - q), (0, dtp - dt)), constant_values=TARG_PAD)
    cand_spec = pl.BlockSpec((block_q, block_d), lambda iq, i1, i2: (iq, i1))
    row_spec = pl.BlockSpec((block_q, 1), lambda iq, i1, i2: (iq, 0))
    in_specs = [
        cand_spec,
        pl.BlockSpec((block_q, block_d), lambda iq, i1, i2: (iq, i2)),
    ]
    args = [cand, targ]
    for x, pad in extra:
        if x.ndim == 2:  # per-candidate, laid out like cand
            x = jnp.pad(x, ((0, qp - q), (0, dcp - dc)), constant_values=pad)
            in_specs.append(cand_spec)
        else:  # per-query column
            x = jnp.pad(x, (0, qp - q), constant_values=pad)[:, None]
            in_specs.append(row_spec)
        args.append(x)
    vma = frozenset().union(*(jax.typeof(x).vma for x in args))
    interpret = _resolve_interpret(interpret)
    if interpret and vma:
        # inside shard_map the HLO interpreter evaluates the kernel on
        # device-varying blocks without tracking their mesh axes, so
        # check_vma rejects the kernel's own constants; the TPU
        # interpreter simulates each device and tracks them
        interpret = pltpu.InterpretParams()
    out_specs, out_shape = [], []
    for cols in out_cols:
        wide = cols == "cand"
        out_specs.append(cand_spec if wide else row_spec)
        out_shape.append(jax.ShapeDtypeStruct(
            (qp, dcp if wide else 1), jnp.int32, vma=vma
        ))
    return pl.pallas_call(
        kernel,
        grid=(qp // block_q, dcp // block_d, dtp // block_d),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        name=f"intersect_{role}_w{dc}",
    )(*args)


@functools.partial(
    jax.jit, static_argnames=("block_q", "block_d", "interpret")
)
def intersect_pallas(
    cand: jnp.ndarray,
    targ: jnp.ndarray,
    lev_c: jnp.ndarray,
    lev_u: jnp.ndarray,
    *,
    block_q: int = 32,
    block_d: int = 128,
    interpret: bool | None = None,  # None -> auto from jax.default_backend()
):
    """See ref.intersect_ref. Shapes are padded up to block multiples here;
    ``cand`` and ``targ`` may have different widths."""
    q = cand.shape[0]
    c1, c2 = _launch(
        _kernel, "split", cand, targ, [(lev_c, -7), (lev_u, -9)], [1, 1],
        block_q=block_q, block_d=block_d, interpret=interpret,
    )
    return c1[:q, 0], c2[:q, 0]


@functools.partial(
    jax.jit, static_argnames=("block_q", "block_d", "interpret")
)
def intersect_pallas_count(
    cand: jnp.ndarray,
    targ: jnp.ndarray,
    *,
    block_q: int = 32,
    block_d: int = 128,
    interpret: bool | None = None,
):
    """Planned count form: ``int32[Q]`` — |cand row ∩ targ row| with no
    level split and no per-candidate mask materialized.  This is
    Algorithm 2's unit of work (after N-hat dedup every hit counts
    exactly once), executed through the same tiling/early-out as
    ``intersect_pallas``; the per-query counter tile is revisited across
    both width grid dims and accumulated in place.  Each row's entries
    must be unique (adjacency lists / transposed sublists are), so a
    candidate is counted in at most one target tile.
    """
    (cnt,) = _launch(
        _count_kernel, "count", cand, targ, [], [1],
        block_q=block_q, block_d=block_d, interpret=interpret,
    )
    return cnt[: cand.shape[0], 0]


@functools.partial(
    jax.jit, static_argnames=("block_q", "block_d", "interpret")
)
def intersect_pallas_hits(
    cand: jnp.ndarray,
    targ: jnp.ndarray,
    *,
    block_q: int = 32,
    block_d: int = 128,
    interpret: bool | None = None,
):
    """Membership variant for triangle *finding*: ``bool[Q, Dc]`` marking
    which candidates appear in the target row.  Same tiling/early-out as
    ``intersect_pallas``; the (BQ, BDc) hit tile is revisited across the
    target grid dim and OR-accumulated in place."""
    q, dc = cand.shape
    (hit,) = _launch(
        _hits_kernel, "hits", cand, targ, [], ["cand"],
        block_q=block_q, block_d=block_d, interpret=interpret,
    )
    return hit[:q, :dc] > 0
