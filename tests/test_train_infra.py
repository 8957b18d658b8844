"""Trainer, checkpoint/restart, elastic reshard, optimizers, compression."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import data as synth
from repro.configs.registry import arch_module
from repro.launch import steps as steps_mod
from repro.train import checkpoint as ckpt
from repro.train.data import LMStream
from repro.train.optimizer import (
    OptConfig, adafactor_init, adafactor_update, clip_by_global_norm,
    opt_init, opt_update, schedule,
)
from repro.train.trainer import Trainer


def _tiny_setup():
    cfg = arch_module("smollm-135m").SMOKE
    params = steps_mod.init_for("smollm-135m", cfg, jax.random.key(0))
    loss = steps_mod.lm_loss(cfg)
    return cfg, params, loss


def test_checkpoint_roundtrip_and_restart(tmp_path):
    cfg, params, loss = _tiny_setup()
    opt_cfg = OptConfig(lr=1e-3, warmup=1, total_steps=20)
    tr = Trainer(loss, params, opt_cfg, ckpt_dir=tmp_path, cfg=cfg,
                 ckpt_every=3, log_every=100)
    stream = LMStream(cfg, 2, 32, seed=1)
    tr.fit(stream, 5)
    assert ckpt.latest_step(tmp_path) == 5
    # simulate a crash + relaunch: fresh trainer restores step AND cursor
    tr2 = Trainer(loss, params, opt_cfg, ckpt_dir=tmp_path, cfg=cfg,
                  log_every=100)
    assert tr2.maybe_restore()
    assert tr2.step_num == 5 and tr2.cursor == 5
    p_a = jax.tree.leaves(tr.params)[0]
    p_b = jax.tree.leaves(tr2.params)[0]
    np.testing.assert_array_equal(np.asarray(p_a), np.asarray(p_b))
    # continue training from the restored state
    tr2.fit(LMStream(cfg, 2, 32, seed=1), 2)
    assert tr2.step_num == 7


def test_checkpoint_rejects_wrong_config(tmp_path):
    cfg, params, loss = _tiny_setup()
    opt_cfg = OptConfig()
    state = {"params": params, "opt": opt_init(opt_cfg, params)}
    ckpt.save(tmp_path, 1, state, cfg=cfg)
    with pytest.raises(ValueError, match="different config"):
        ckpt.load(tmp_path, state, cfg="other-config")


def test_checkpoint_elastic_reshard(tmp_path):
    """Save from one (trivial) mesh, restore onto another — logical arrays
    make the checkpoint mesh-independent."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    cfg, params, loss = _tiny_setup()
    state = {"params": params}
    ckpt.save(tmp_path, 1, state, cfg=cfg, mesh_shape={"data": 1})
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1), ("data",))
    shardings = jax.tree.map(lambda _: NamedSharding(mesh, P()), state)
    restored, manifest = ckpt.load(tmp_path, state, cfg=cfg,
                                   shardings=shardings)
    assert manifest["step"] == 1
    leaf = jax.tree.leaves(restored["params"])[0]
    assert isinstance(leaf.sharding, NamedSharding)


def test_checkpoint_retention(tmp_path):
    cfg, params, _ = _tiny_setup()
    for step in range(1, 6):
        ckpt.save(tmp_path, step, {"p": params}, keep=2)
    import pathlib

    files = sorted(pathlib.Path(tmp_path).glob("step_*.npz"))
    assert len(files) == 2
    assert files[-1].name == "step_00000005.npz"


def test_watchdog_raises():
    cfg, params, loss = _tiny_setup()
    tr = Trainer(loss, params, OptConfig(), watchdog_s=0.0, log_every=100)
    with pytest.raises(TimeoutError):
        tr.fit(LMStream(cfg, 2, 32), 1)


def test_adafactor_memory_is_sublinear():
    cfg, params, loss = _tiny_setup()
    adam = opt_init(OptConfig(kind="adamw"), params)
    fac = opt_init(OptConfig(kind="adafactor"), params)
    size = lambda t: sum(x.size for x in jax.tree.leaves(t))
    assert size(fac) < 0.2 * size(adam)
    # one update step works and moves params
    tokens, labels = synth.lm_batch(cfg, 2, 16)
    grads = jax.grad(loss)(params, tokens, labels)
    p2, s2, gn = opt_update(OptConfig(kind="adafactor"), grads, fac, params)
    assert float(gn) > 0
    assert max(
        float(jnp.abs(a - b).max()) for a, b in
        zip(jax.tree.leaves(params), jax.tree.leaves(p2))
    ) > 0


def test_schedule_and_clip():
    oc = OptConfig(lr=1.0, warmup=10, total_steps=110)
    assert float(schedule(oc, jnp.int32(5))) == pytest.approx(0.5)
    assert float(schedule(oc, jnp.int32(10))) == pytest.approx(1.0)
    assert float(schedule(oc, jnp.int32(110))) == pytest.approx(0.0, abs=1e-6)
    g = {"a": jnp.full((3,), 100.0)}
    clipped, gn = clip_by_global_norm(g, 1.0)
    assert float(jnp.linalg.norm(clipped["a"])) == pytest.approx(1.0, rel=1e-5)


def test_int8_compressed_psum_single_device():
    """Numerical property of the quantizer on a trivial 1-device mesh."""
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.train.trainer import int8_compressed_psum

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1), ("d",))
    g = {"w": jnp.asarray(np.random.default_rng(0).standard_normal(128),
                          jnp.float32)}

    def f(tree):
        return int8_compressed_psum(tree, "d")

    out = jax.jit(
        jax.shard_map(f, mesh=mesh, in_specs=({"w": P()},),
                  out_specs={"w": P()}),
    )(g)
    err = float(jnp.abs(out["w"] - g["w"]).max())
    scale = float(jnp.abs(g["w"]).max())
    assert err <= scale / 127.0 + 1e-6
