"""``chip_smoke.py`` rehearsed on the CPU: its phase functions at a tiny
size (RMAT scale 8, 8 requests) with the Pallas kernels in interpret
mode — ``main()`` itself never runs them so, and refuses to run at all
without a TPU.  Also the compile-cache helper every entry point calls."""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest
from conftest import FIXTURES, nx_triangles

from repro.graph.generators import rmat
from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"
INTERPRET = {"backend": "pallas", "interpret": True}


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def rmat8(smoke):
    edges, n = rmat(8, 16, seed=0)
    return edges, n, smoke.reference_counts(edges, n)


@pytest.mark.parametrize("name", ["karate", "rmat8", "complete9"])
def test_reference_matches_networkx(smoke, name):
    import networkx as nx

    edges, n = FIXTURES[name]
    tri, per_vertex = smoke.reference_counts(edges, n)
    assert tri == nx_triangles(edges, n)
    g = nx.Graph()
    g.add_nodes_from(range(n))
    g.add_edges_from(np.asarray(edges))
    g.remove_edges_from(nx.selfloop_edges(g))
    want = nx.triangles(g)
    assert per_vertex.tolist() == [want[v] for v in range(n)]


def test_local_phase(smoke, rmat8):
    edges, n, ref = rmat8
    row = smoke.phase_local(edges, n, ref=ref, query_chunk=64, **INTERPRET)
    assert row["phase"] == "local"
    assert row["triangles"] == ref[0]
    assert (row["backend"], row["interpret"]) == ("pallas", True)
    assert row["c1"] + row["c2"] // 3 == ref[0]
    json.dumps(row)  # one JSON line


def test_local_phase_rejects_a_wrong_reference(smoke, rmat8):
    edges, n, (tri, per_vertex) = rmat8
    with pytest.raises(smoke.SmokeFailure, match="reference"):
        smoke.phase_local(edges, n, ref=(tri + 1, per_vertex),
                          query_chunk=64, **INTERPRET)


def test_serve_phase(smoke):
    row = smoke.phase_serve(8, **INTERPRET)
    assert (row["requests"], row["exact"]) == (8, 8)
    assert "approx" not in row["by_route"]
    json.dumps(row)


def test_distributed_phase_on_four_cpu_devices():
    """The ``--chips 4`` phase on four virtual CPU devices (the device
    count must be forced before JAX starts, hence the subprocess)."""
    body = (
        "import json, jax, chip_smoke as s\n"
        "from repro.graph.generators import rmat\n"
        "e, n = rmat(8, 16, seed=0)\n"
        "print(json.dumps(s.phase_distributed(\n"
        "    e, n, devices=jax.devices()[:4], ref=s.reference_counts(e, n),\n"
        "    query_chunk=64, backend='pallas', interpret=True)))\n"
    )
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "PYTHONPATH": os.pathsep.join(
            [str(ROOT), str(ROOT / "src"), os.environ.get("PYTHONPATH", "")]
        ),
    }
    out = subprocess.run([sys.executable, "-c", body], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    row = json.loads(out.stdout.strip().splitlines()[-1])
    assert row["devices"] == len(row["per_device"]) == 4
    assert row["triangles"] == row["local_triangles"] == sum(
        row["per_device"])
    assert set(row["comm_bytes"]) >= {"bfs", "transpose", "hedge"}


def test_main_refuses_without_a_tpu(smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main([]) != 0
    assert '"ok": true' not in capsys.readouterr().out


def test_lone_script_fails(tmp_path):
    """Copied out of the checkout, the script cannot import the engine
    and must exit non-zero without an ``ok`` line."""
    shutil.copy(SCRIPT, tmp_path / SCRIPT.name)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run([sys.executable, SCRIPT.name], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout


def test_compile_cache_leaves_the_environment_dir_to_jax(monkeypatch,
                                                         tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_a_fixed_checkout_dir(monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.enable_compile_cache()
        assert got == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
        assert compile_cache.enable_compile_cache() == got  # not per-call
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()
