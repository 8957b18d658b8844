"""The benchmark's graph generators are seeded and match the published
parameters of the deployments they stand for."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import graphs, reference  # noqa: E402


def config(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


def small(name, **kw):
    return dict(config(name), **kw)


@pytest.mark.parametrize("name", ["gap-kron-s15", "gap-urand-s15"])
def test_gap_graphs_are_seeded_at_edge_factor_16(name):
    cfg = small(name, scale=10)
    gen = graphs.load(cfg["generator"])
    ((a, n),) = gen.generate(cfg, 5)
    ((b, _),) = gen.generate(cfg, 5)
    ((c, _),) = gen.generate(cfg, 6)
    assert n == 1024 and a.shape == (16 * 1024, 2)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert a.min() >= 0 and a.max() < n


def test_kron_config_is_graph500():
    cfg = config("gap-kron-s15")
    assert (cfg["a"], cfg["b"], cfg["c"], cfg["edge_factor"]) == (
        0.57, 0.19, 0.19, 16)


def _degrees(edges, n):
    e = np.unique(np.sort(edges, axis=1), axis=0)
    e = e[e[:, 0] != e[:, 1]]
    return np.bincount(e.ravel(), minlength=n)


def test_kron_is_skewed_and_urand_is_not():
    """At scale 15 the Kronecker graph has hubs of thousands; the
    uniform graph's largest degree stays under 80 (mean 32)."""
    kron = config("gap-kron-s15")
    urand = config("gap-urand-s15")
    ((ke, n),) = graphs.load("kron").generate(kron, kron["graph_seed"])
    ((ue, _),) = graphs.load("urand").generate(urand, urand["graph_seed"])
    kd, ud = _degrees(ke, n), _degrees(ue, n)
    assert kd.max() > 3000 and kd.max() > 100 * np.median(kd[kd > 0])
    assert ud.max() < 80 and 30 < ud.mean() < 33


def test_egonets_match_collab_means():
    """COLLAB: 74.49 vertices and 2,457.78 edges per graph on average,
    32 to 492 vertices; the ego links to every alter."""
    cfg = config("collab-egonets")
    pool = graphs.load("egonets").generate(cfg, cfg["graph_seed"])
    assert len(pool) == cfg["count"]
    ns = np.array([n for _, n in pool])
    ms = np.array([len(e) for e, _ in pool])
    assert abs(ns.mean() - 74.49) < 0.01 * 74.49
    assert abs(ms.mean() - 2457.78) < 0.02 * 2457.78
    assert ns.min() >= 32 and ns.max() <= 492
    for e, n in pool[::64]:
        assert set(e[e[:, 0] == 0, 1]) == set(range(1, n))
    again = graphs.load("egonets").generate(cfg, cfg["graph_seed"])
    assert all(np.array_equal(a, b) for (a, _), (b, _) in zip(pool, again))


def test_relabel_keeps_root_degrees_and_triangles():
    cfg = small("gap-kron-s15", scale=8)
    ((e, n),) = graphs.load("kron").generate(cfg, 1)
    r1 = graphs.relabel(e, n, np.random.default_rng(2**31 + 7))
    r2 = graphs.relabel(e, n, np.random.default_rng(2**31 + 7))
    r3 = graphs.relabel(e, n, np.random.default_rng(3))
    assert np.array_equal(r1, r2) and not np.array_equal(r1, r3)
    d0, d1 = _degrees(e, n), _degrees(r1, n)
    assert d0[0] == d1[0]
    assert np.array_equal(np.sort(d0), np.sort(d1))
    assert reference.triangles(e, n) == reference.triangles(r1, n)
