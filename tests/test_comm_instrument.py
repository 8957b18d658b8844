"""Measured communication (core/comm_instrument): the analytic CommTally
threaded through the shard program, the per-collective volumes extracted
from the lowered jaxpr/HLO, and the closed-form wire model must agree —
and the serving layer's distributed route must answer over-budget
requests bit-identically to the sequential pipeline."""
from __future__ import annotations

import numpy as np
import pytest

from repro.core import comm_model as cm
from repro.core.comm_instrument import (
    choose_hedge_mode,
    hedge_round_buffer_bytes,
    hlo_collective_counts,
    tally_comm,
)
from tests.test_parallel_tc import run_multidevice


def test_tally_matches_wire_model_formulas():
    """tally_comm and wire_bytes_report are the same accounting by
    construction — any (n, p, caps, sweeps) must agree term by term."""
    rng = np.random.default_rng(0)
    for _ in range(20):
        n = int(rng.integers(2, 5000))
        p = int(rng.integers(1, 17))
        cap_chunk = int(rng.integers(4, 4096))
        cap_hedge = int(rng.integers(1, 8192))
        sweeps = int(rng.integers(1, 40))
        for mode in ("allgather", "ring"):
            for fd in ("int32", "uint8"):
                tally = tally_comm(
                    n=n, p=p, cap_chunk=cap_chunk, cap_hedge=cap_hedge,
                    mode=mode, frontier_dtype=fd, sweeps=sweeps,
                ).phase_bytes()
                model = cm.wire_bytes_report(
                    n, p, cap_chunk=cap_chunk, cap_hedge=cap_hedge,
                    n_levels=sweeps, mode=mode, frontier_dtype=fd,
                )
                for ph in cm.WIRE_PHASES:
                    assert tally[ph] == model[ph], (ph, mode, fd, p)
        # p == 1 must mean zero communication in every phase
        z = tally_comm(n=n, p=1, cap_chunk=cap_chunk, cap_hedge=cap_hedge,
                       mode="ring", frontier_dtype="int32", sweeps=sweeps)
        assert z.total == 0
    # a phase beyond the int32 odometer saturates instead of crashing
    # the trace (the big-graph serving route's regime) — and the exact
    # BFS parts still resolve the sweep product with host arithmetic
    big = tally_comm(n=1 << 20, p=8, cap_chunk=1 << 20, cap_hedge=1 << 27,
                     mode="allgather", frontier_dtype="int32", sweeps=9)
    from repro.core.comm_instrument import TALLY_SAT_BYTES
    assert big.phase_bytes()["hedge"] == TALLY_SAT_BYTES
    assert big.phase_bytes()["bfs"] == 10 * cm.allreduce_wire_bytes(
        (1 << 20) * 4, 8)


def test_hedge_mode_router_policy():
    """Both modes move equal wire volume, so the router picks by live
    buffer: allgather until the gathered block exceeds the limit."""
    m2, p = 1 << 20, 8
    gathered = hedge_round_buffer_bytes(m2, p, "allgather")
    ring = hedge_round_buffer_bytes(m2, p, "ring")
    assert gathered == p * ring
    assert choose_hedge_mode(m2, p,
                             gather_buffer_limit_bytes=gathered) == "allgather"
    assert choose_hedge_mode(m2, p,
                             gather_buffer_limit_bytes=gathered - 1) == "ring"


def test_shard_fn_fallback_plan_respects_backend_knobs():
    """Regression: build_tc_shard_fn used to be handed only the default
    backend/interpret/frontier_dtype by parallel_triangle_count — the
    fallback-plan path must carry the caller's choice."""
    from repro.core.parallel_tc import build_tc_shard_fn

    fn, _ = build_tc_shard_fn(
        n=64, m2=512, p=2, intersect_backend="pallas", interpret=True,
        frontier_dtype="uint8",
    )
    assert fn.keywords["hplan"].backend == "pallas"
    assert fn.keywords["hplan"].interpret is True
    assert fn.keywords["frontier_dtype"] == "uint8"


#: one StableHLO op of each collective kind, as ``Lowered.as_text()``
#: prints them on two devices
HLO_OPS = {
    "all_gather": '%2 = "stablehlo.all_gather"(%1) <{all_gather_dim = 0 '
                  ': i64, replica_groups = dense<[[0, 1]]> : tensor<1x2xi64>'
                  '}> : (tensor<1x4xf32>) -> tensor<2x4xf32>',
    "all_to_all": '%6 = "stablehlo.all_to_all"(%5) <{concat_dimension = 0 '
                  ': i64, split_count = 2 : i64, split_dimension = 0 : i64}>'
                  ' : (tensor<2x2xf32>) -> tensor<2x2xf32>',
    "ppermute": '%4 = "stablehlo.collective_permute"(%arg1) <{'
                'source_target_pairs = dense<[[0, 1], [1, 0]]> : '
                'tensor<2x2xi64>}> : (tensor<4xf32>) -> tensor<4xf32>',
    "all_reduce": '%3 = "stablehlo.all_reduce"(%arg1) <{replica_groups = '
                  'dense<[[0, 1]]> : tensor<1x2xi64>}> ({',
}
#: not a collective, though its name shares a prefix with one
HLO_LOCAL = ("%7 = stablehlo.reduce(%2 init: %cst) applies stablehlo.add "
             "across dimensions = [0, 1] : (tensor<2x4xf32>, tensor<f32>) "
             "-> tensor<f32>")


@pytest.mark.parametrize("kind", sorted(HLO_OPS))
def test_hlo_collective_counts_census(kind):
    """Each of the four kinds counts once, the local reduce not at all,
    and taking a kind's op out of the module zeroes that kind only."""
    every = dict.fromkeys(HLO_OPS, 1)
    assert hlo_collective_counts(
        "\n".join([*HLO_OPS.values(), HLO_LOCAL])) == every
    rest = [op for k, op in HLO_OPS.items() if k != kind]
    assert hlo_collective_counts("\n".join([*rest, HLO_LOCAL])) == {
        **every, kind: 0}


@pytest.mark.slow
def test_measured_equals_tally_and_model_multidevice():
    """On 1/2/4/8 host devices, both exchange modes: the per-phase
    volumes extracted from the lowered program equal the analytic
    CommTally exactly, sit inside the modeled envelope, and the ring /
    allgather hedge totals are equal while ring's per-round buffer is
    p x smaller."""
    out = run_multidevice(
        """
        import jax, numpy as np
        from jax.sharding import Mesh
        from repro.graph import generators as gen
        from repro.graph.csr import from_edges
        from repro.core.parallel_tc import parallel_triangle_count
        from repro.core import comm_instrument as ci
        from repro.core import comm_model as cm

        edges, n = gen.rmat(8, 8, seed=1)
        g = from_edges(edges, n)
        m2 = int(jax.device_get(g.n_edges_dir))
        devs = np.array(jax.devices())
        hedge = {}
        for p in (1, 2, 4, 8):
            mesh = Mesh(devs[:p].reshape(p), ('p',))
            for mode in ('allgather', 'ring'):
                res = parallel_triangle_count(g, mesh, mode=mode)
                tally = res.comm.phase_bytes()
                sweeps = int(jax.device_get(res.comm.bfs_sweeps))
                rep = ci.comm_report(n, m2, p, sweeps=sweeps, mode=mode)
                for ph, row in rep['phases'].items():
                    assert row['measured'] == tally[ph], (p, mode, ph, row, tally)
                    assert row['measured'] == row['modeled'], (p, mode, ph)
                # an upper-bound level count makes the model an envelope
                env = cm.wire_bytes_report(
                    n, p, cap_chunk=0, cap_hedge=0, n_levels=sweeps + 4,
                    mode=mode)
                assert env['bfs'] >= tally['bfs']
                hedge[(p, mode)] = tally['hedge']
            assert hedge[(p, 'ring')] == hedge[(p, 'allgather')], p
            if p > 1:
                ga = ci.hedge_round_buffer_bytes(m2, p, 'allgather')
                ri = ci.hedge_round_buffer_bytes(m2, p, 'ring')
                assert ga == p * ri, p
        # size-collision regression: a graph tiny enough that
        # cap_hedge == p must still attribute the hedge gathers to
        # hedge (structural, not shape-based, classification)
        e2 = np.array([[i, i + 1] for i in range(6)])
        g2 = from_edges(e2, 7)
        m2b = int(jax.device_get(g2.n_edges_dir))
        mesh4 = Mesh(devs[:4].reshape(4), ('p',))
        r2 = parallel_triangle_count(g2, mesh4)
        t2 = r2.comm.phase_bytes()
        rep2 = ci.comm_report(
            7, m2b, 4, sweeps=int(jax.device_get(r2.comm.bfs_sweeps)))
        assert rep2['phases']['hedge']['measured'] == t2['hedge'] > 0
        assert rep2['phases']['splitter']['measured'] == t2['splitter']

        # uint8 frontiers move 4x fewer per-sweep BFS bytes
        mesh = Mesh(devs[:4].reshape(4), ('p',))
        r32 = parallel_triangle_count(g, mesh, frontier_dtype='int32')
        r8 = parallel_triangle_count(g, mesh, frontier_dtype='uint8')
        assert int(r8.triangles) == int(r32.triangles)
        s = int(jax.device_get(r32.comm.bfs_sweeps))
        fixed = cm.allreduce_wire_bytes(n * 4, 4)
        b32 = r32.comm.phase_bytes()['bfs'] - fixed
        b8 = r8.comm.phase_bytes()['bfs'] - fixed
        assert b32 == 4 * b8 and b8 == s * cm.allreduce_wire_bytes(n, 4)
        print('DONE')
        """
    )
    assert "DONE" in out


@pytest.mark.slow
def test_serve_routes_over_budget_to_distributed():
    """Acceptance: a mixed stream containing over-budget graphs is
    answered with per-request triangle counts bit-identical to
    triangle_count, over-budget requests on the distributed route,
    nothing overflow-flagged."""
    out = run_multidevice(
        """
        import numpy as np
        from repro.launch.serve_tc import TriangleServer, synth_requests
        from repro.graph.csr import BudgetGrid, from_edges
        from repro.graph import generators as gen
        from repro.core.sequential import triangle_count

        grid = BudgetGrid(max_nodes=256, max_slots=2048)
        srv = TriangleServer(batch_size=4, grid=grid)
        reqs = synth_requests(10, seed=3)
        reqs.insert(3, gen.rmat(9, 8, seed=7))   # n=512: over-budget
        reqs.append(gen.rmat(9, 4, seed=8))
        want = [int(triangle_count(from_edges(e, n)).triangles)
                for e, n in reqs]
        for e, n in reqs:
            srv.submit(e, n)
        res = {r.request_id: r for r in srv.drain()}
        assert len(res) == len(reqs)
        for i in range(len(reqs)):
            assert res[i].triangles == want[i], (i, res[i], want[i])
            assert not res[i].overflow, i
        # unified TriangleReport contract: no -1 sentinel — the
        # distributed route answers with c1/c2 = None + provenance
        assert res[3].route == 'distributed'
        assert res[3].c1 is None and res[3].c2 is None
        assert res[3].report is not None
        assert res[3].report.route == 'distributed'
        assert res[3].report.comm is not None
        batched3 = [r for r in res.values() if r.route == 'batched']
        assert all(r.c1 is not None and r.c2 is not None for r in batched3)
        assert res[len(reqs) - 1].route == 'distributed'
        batched = [r for r in res.values() if r.route == 'batched']
        assert len(batched) == len(reqs) - 2
        assert srv.summary()['distributed_requests'] == 2
        print('DONE')
        """
    )
    assert "DONE" in out
