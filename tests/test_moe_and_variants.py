"""MoE dispatch equivalence (gspmd vs explicit-a2a), transformer execution
variants (chunked attention, bf16, unroll), and registry/cell plumbing —
the §Perf machinery must be semantics-preserving."""
from __future__ import annotations

import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import all_cells, arch_module, opt_overrides
from repro.models.transformer import LMConfig, forward, init_params, loss_fn

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_all_cells_enumeration():
    cells = all_cells()
    assert len(cells) == 40  # 10 archs x 4 shapes
    assert ("gemma3-1b", "long_500k") in cells


def test_opt_overrides_shape():
    assert opt_overrides("smollm-135m")["attn_impl"] == "chunked"
    assert opt_overrides("qwen2-moe-a2.7b")["moe.dispatch"] == "a2a"
    assert opt_overrides("cover-edge-tc")["frontier_dtype"] == "uint8"
    assert opt_overrides("gat-cora") == {}


@pytest.fixture(scope="module")
def tiny_lm():
    cfg = LMConfig(name="tiny", n_layers=4, d_model=64, n_heads=4,
                   n_kv_heads=2, d_head=16, d_ff=128, vocab=256, window=16,
                   global_every=2, qk_norm=True)
    params = init_params(jax.random.key(0), cfg)
    toks = jax.random.randint(jax.random.key(1), (2, 64), 0, 256)
    base, _ = forward(cfg, params, toks)
    return cfg, params, toks, base


@pytest.mark.parametrize("over", [
    dict(attn_impl="chunked", attn_chunk=16),
    dict(attn_impl="chunked", attn_chunk=16, attn_unroll=True),
    dict(attn_impl="chunked", attn_chunk=24),  # non-divisor chunk
    dict(remat="none"),
])
def test_lm_variants_match_dense(tiny_lm, over):
    cfg, params, toks, base = tiny_lm
    out, _ = forward(dataclasses.replace(cfg, **over), params, toks)
    np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                               rtol=2e-4, atol=2e-4)


def test_bf16_variant_close_and_trains(tiny_lm):
    cfg, params, toks, base = tiny_lm
    v = dataclasses.replace(cfg, attn_impl="chunked", attn_chunk=16,
                            act_dtype="bfloat16")
    out, _ = forward(v, params, toks)
    err = float(jnp.abs(out.astype(jnp.float32) - base).max())
    assert err < 0.5
    g = jax.grad(lambda p: loss_fn(v, p, toks, toks))(params)
    assert all(bool(jnp.isfinite(x).all()) for x in jax.tree.leaves(g))


@pytest.mark.slow
def test_moe_a2a_equals_gspmd_multidevice():
    body = """
    import jax, jax.numpy as jnp, dataclasses
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.models.moe import MoEConfig, moe_ffn, moe_ffn_init

    mesh = jax.make_mesh((2, 4), ("data", "model"))
    cfg_g = MoEConfig(n_experts=8, top_k=2, d_ff_expert=32, d_ff_shared=64,
                      capacity_factor=16.0)
    cfg_a = dataclasses.replace(cfg_g, dispatch="a2a")
    params = moe_ffn_init(jax.random.key(0), cfg_g, 16)
    x = jax.random.normal(jax.random.key(1), (4, 8, 16))
    with jax.set_mesh(mesh):
        xs = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
        ps = jax.device_put(params, jax.tree.map(
            lambda _: NamedSharding(mesh, P()), params))
        outg, _ = jax.jit(lambda p, xx: moe_ffn(p, cfg_g, xx))(ps, xs)
        outa, _ = jax.jit(lambda p, xx: moe_ffn(p, cfg_a, xx))(ps, xs)
        err = float(jnp.abs(outg - outa).max())
        assert err < 1e-5, err
        # padded-expert variant (qwen2 pattern: 6 logical on 8 physical)
        cfg_p = dataclasses.replace(
            cfg_a, n_experts=6, pad_experts_to=8, capacity_factor=16.0)
        out_p, _ = jax.jit(lambda p, xx: moe_ffn(p, cfg_p, xx))(ps, xs)
        assert bool(jnp.isfinite(out_p).all())
        g = jax.jit(jax.grad(lambda p, xx: moe_ffn(p, cfg_a, xx)[0].sum()))(
            ps, xs)
        assert all(bool(jnp.isfinite(v).all()) for v in jax.tree.leaves(g))
    print("MOE_A2A_OK")
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                         env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "MOE_A2A_OK" in out.stdout


@pytest.mark.slow
def test_tc_uint8_frontier_and_tuned_knobs():
    body = """
    import jax, numpy as np, networkx as nx
    from jax.sharding import Mesh
    from repro.graph import generators as gen
    from repro.graph.csr import from_edges
    from repro.core.parallel_tc import parallel_triangle_count
    from repro.core.bfs import bfs_levels

    mesh = Mesh(np.array(jax.devices()).reshape(8), ('p',))
    edges, n = gen.rmat(8, 8, seed=1)
    g = from_edges(edges, n)
    G = nx.Graph(); G.add_nodes_from(range(n))
    G.add_edges_from(np.asarray(edges))
    G.remove_edges_from(nx.selfloop_edges(G))
    want = sum(nx.triangles(G).values()) // 3
    # tuned slack is exact; d_pad guard trips-or-matches
    res = parallel_triangle_count(g, mesh, mode='ring', slack=2.0)
    assert int(res.triangles) == want and not bool(res.transpose_overflow)
    res64 = parallel_triangle_count(g, mesh, mode='ring', d_pad=16)
    assert bool(res64.transpose_overflow) or int(res64.triangles) == want
    print("TC_VARIANTS_OK")
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                         env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "TC_VARIANTS_OK" in out.stdout


def test_uint8_frontier_levels_match_single_device():
    from repro.core.bfs import bfs_levels
    from repro.graph import generators as gen
    from repro.graph.csr import from_edges

    edges, n = gen.karate()
    g = from_edges(edges, n)
    a = bfs_levels(g.src, g.dst, n)
    # frontier_dtype only matters with an axis; single-device sanity:
    b = bfs_levels(g.src, g.dst, n, frontier_dtype="uint8")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
