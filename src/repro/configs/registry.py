"""Central (architecture x input-shape) cell registry.

A ``Cell`` is everything the dry-run / trainer needs to lower one program:
the step callable, abstract input structs, input shardings for the given
mesh, and roofline metadata (MODEL_FLOPS).  40 assigned cells (10 archs x
their 4 shapes) + the paper's own TC workload.
"""
from __future__ import annotations

import dataclasses
import importlib
import math
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.lm import LM_SHAPES, LONG_CONTEXT_OK
from repro.distributed import sharding as sh
from repro.launch import steps
from repro.models.gnn.common import GraphBatch
from repro.train.optimizer import OptConfig, opt_init

ARCH_MODULES = {
    "smollm-135m": "repro.configs.smollm_135m",
    "gemma3-4b": "repro.configs.gemma3_4b",
    "gemma3-1b": "repro.configs.gemma3_1b",
    "qwen2-moe-a2.7b": "repro.configs.qwen2_moe_a2_7b",
    "phi3.5-moe-42b-a6.6b": "repro.configs.phi35_moe",
    "gatedgcn": "repro.configs.gatedgcn",
    "gat-cora": "repro.configs.gat_cora",
    "dimenet": "repro.configs.dimenet",
    "schnet": "repro.configs.schnet",
    "bst": "repro.configs.bst",
    "cover-edge-tc": "repro.configs.cover_edge_tc",
}

ASSIGNED_ARCHS = [a for a in ARCH_MODULES if a != "cover-edge-tc"]


def arch_module(name: str):
    return importlib.import_module(ARCH_MODULES[name])


@dataclasses.dataclass
class Cell:
    arch: str
    shape: str
    kind: str
    fn: Optional[Callable]
    args: tuple
    in_shardings: Any
    out_shardings: Any
    model_flops: float
    skip_reason: Optional[str] = None
    mesh: Optional[Mesh] = None  # override (TC uses its own flat 1-D mesh)

    @property
    def skipped(self) -> bool:
        return self.skip_reason is not None


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _to_ns(mesh: Mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def _eval_params(arch: str, cfg):
    return jax.eval_shape(
        lambda: steps.init_for(arch, cfg, jax.random.key(0))
    )


# ------------------------------------------------------------------- LM

def _lm_model_flops(cfg, kind: str, batch: int, s_len: int) -> float:
    """Algorithmically-useful FLOPs: 2*(active non-embedding params)*token
    for the dense path, exact causal/windowed attention token counts, and
    the LM head; train = 3x forward (bwd), ignoring remat recompute (which
    is what the useful/compiled ratio is meant to expose)."""
    n_embed = cfg.vocab * cfg.d_model * (1 if cfg.tie_embeddings else 2)
    n_body = cfg.active_param_count() - n_embed

    def attn_len(w):
        if w is None or w >= s_len:
            return s_len * s_len / 2
        return s_len * w - w * w / 2

    if kind in ("train", "prefill"):
        tokens = batch * s_len
        attn_positions = sum(attn_len(w) for w in cfg.layer_windows)
        fwd = (
            2.0 * n_body * tokens
            + 4.0 * batch * cfg.n_heads * cfg.d_head * attn_positions
            + 2.0 * tokens * cfg.d_model * cfg.vocab
        )
        return 3.0 * fwd if kind == "train" else fwd
    # decode: one token per sequence against the cache
    lens = sum(
        s_len if w is None else min(w, s_len) for w in cfg.layer_windows
    )
    return (
        2.0 * n_body * batch
        + 4.0 * batch * cfg.n_heads * cfg.d_head * lens
        + 2.0 * batch * cfg.d_model * cfg.vocab
    )


def _lm_cell(arch: str, cfg, shape_name: str, mesh: Mesh,
             opt_cfg: OptConfig) -> Cell:
    info = LM_SHAPES[shape_name]
    kind, s_len, batch = info["kind"], info["seq_len"], info["global_batch"]
    if shape_name == "long_500k" and cfg.name not in LONG_CONTEXT_OK:
        return Cell(arch, shape_name, kind, None, (), None, None, 0.0,
                    skip_reason="pure full-attention arch; 512k dense-cache "
                    "decode excluded")
    d_axes = sh.data_axes(mesh)
    params = _eval_params(arch, cfg)
    pspecs = sh.lm_param_specs(params, mesh)
    flops = _lm_model_flops(cfg, kind, batch, s_len)
    if kind == "train":
        opt = jax.eval_shape(lambda p: opt_init(opt_cfg, p), params)
        ospecs = sh.opt_state_specs(pspecs, opt)
        tokens = _sds((batch, s_len), jnp.int32)
        fn = steps.lm_train_step(cfg, opt_cfg)
        args = (params, opt, tokens, tokens)
        in_sh = (
            _to_ns(mesh, pspecs), _to_ns(mesh, ospecs),
            NamedSharding(mesh, P(d_axes, None)),
            NamedSharding(mesh, P(d_axes, None)),
        )
        out_sh = (_to_ns(mesh, pspecs), _to_ns(mesh, ospecs), None)
    elif kind == "prefill":
        tokens = _sds((batch, s_len), jnp.int32)
        fn = steps.lm_prefill_step(cfg, max_len=s_len)
        args = (params, tokens)
        in_sh = (_to_ns(mesh, pspecs), NamedSharding(mesh, P(d_axes, None)))
        out_sh = None
    else:  # decode
        cache_shape = (cfg.n_layers, batch, s_len, cfg.n_kv_heads, cfg.d_head)
        cache_dtype = jnp.dtype(cfg.act_dtype)  # bf16 cache when act bf16
        cache = (_sds(cache_shape, cache_dtype), _sds(cache_shape, cache_dtype))
        token = _sds((batch, 1), jnp.int32)
        index = _sds((), jnp.int32)
        fn = steps.lm_decode_step(cfg)
        args = (params, cache, token, index)
        cspec = sh.lm_cache_spec(mesh, batch)
        cache_ns = (NamedSharding(mesh, cspec), NamedSharding(mesh, cspec))
        n_data = math.prod(mesh.shape[a] for a in d_axes) if d_axes else 1
        tok_spec = P(d_axes, None) if batch >= n_data else P(None, None)
        in_sh = (
            _to_ns(mesh, pspecs), cache_ns,
            NamedSharding(mesh, tok_spec), NamedSharding(mesh, P()),
        )
        out_sh = (None, cache_ns)
    return Cell(arch, shape_name, kind, fn, args, in_sh, out_sh, flops)


# ------------------------------------------------------------------- GNN

_GNN_FWD_FLOPS = {
    # rough per-layer dense+edge costs (documented in benchmarks/roofline)
    "gatedgcn": lambda cfg, n, e: cfg.n_layers * (5 * n * cfg.d_hidden ** 2
                                                  + 6 * e * cfg.d_hidden) * 2,
    "gat-cora": lambda cfg, n, e: (
        n * cfg.d_in * cfg.d_hidden * cfg.n_heads * 2
        + n * cfg.d_hidden * cfg.n_heads * cfg.n_classes * 2
        + 8 * e * cfg.d_hidden * cfg.n_heads
    ),
    "schnet": lambda cfg, n, e: cfg.n_interactions * (
        4 * n * cfg.d_hidden ** 2 * 2 + 2 * e * cfg.n_rbf * cfg.d_hidden
        + 4 * e * cfg.d_hidden
    ),
    # dimenet takes the ACTUAL triplet budget t (shape-dependent)
    "dimenet": lambda cfg, n, e, t=0: cfg.n_blocks * (
        2 * t * (cfg.d_hidden * cfg.n_bilinear        # w_kj gather-side
                 + cfg.n_spherical * cfg.n_radial * cfg.n_bilinear
                 + cfg.n_bilinear ** 2 * cfg.d_hidden)  # bilinear einsum
        + 6 * e * cfg.d_hidden ** 2 * 2
    ),
}


def _gnn_cell(arch: str, cfg, shape_name: str, mesh: Mesh,
              opt_cfg: OptConfig) -> Cell:
    from repro.configs.gnn import GNN_SHAPES

    info = GNN_SHAPES[shape_name]
    flat = sh.flat_axes(mesh)
    molecular = arch in ("schnet", "dimenet")
    # feature-consuming archs adapt d_in to the shape's dataset
    if not molecular and hasattr(cfg, "d_in"):
        cfg = dataclasses.replace(cfg, d_in=info["d_feat"])
    if shape_name == "minibatch_lg":
        seeds, (f1, f2) = info["batch_nodes"], info["fanout"]
        n = seeds * (1 + f1 + f1 * f2)
        e_slots = seeds * f1 + seeds * f1 * f2
        d_feat = info["d_feat"]
        n_graphs = 1
    elif shape_name == "molecule":
        n = info["n_nodes"] * info["batch"]
        e_slots = 2 * info["n_edges"] * info["batch"]
        d_feat = info["d_feat"]
        n_graphs = info["batch"]
    else:
        n = info["n_nodes"]
        e_slots = 2 * info["n_edges"]
        d_feat = info["d_feat"]
        n_graphs = 1
    # pad edge slots to device multiple for even sharding
    ndev = mesh.devices.size
    e_slots = -(-e_slots // ndev) * ndev
    trip = info["triplet_factor"] * e_slots if arch == "dimenet" else None
    if trip is not None:
        trip = -(-trip // ndev) * ndev
    batch = GraphBatch(
        src=_sds((e_slots,), jnp.int32),
        dst=_sds((e_slots,), jnp.int32),
        node_feat=None if molecular else _sds((n, d_feat), jnp.float32),
        positions=_sds((n, 3), jnp.float32) if molecular else None,
        atom_type=_sds((n,), jnp.int32) if molecular else None,
        graph_id=_sds((n,), jnp.int32),
        labels=_sds((n_graphs,), jnp.float32) if molecular
        else _sds((n,), jnp.int32),
        label_mask=None if molecular else _sds((n,), jnp.bool_),
        trip_kj=_sds((trip,), jnp.int32) if trip else None,
        trip_ji=_sds((trip,), jnp.int32) if trip else None,
    )
    bspec = GraphBatch(
        src=P(flat), dst=P(flat),
        node_feat=None if molecular else P(),
        positions=P() if molecular else None,
        atom_type=P() if molecular else None,
        graph_id=P(),
        labels=P(),
        label_mask=None if molecular else P(),
        trip_kj=P(flat) if trip else None,
        trip_ji=P(flat) if trip else None,
    )
    params = _eval_params(arch, cfg)
    pspecs = sh.gnn_param_specs(params, mesh)
    opt = jax.eval_shape(lambda p: opt_init(opt_cfg, p), params)
    ospecs = sh.opt_state_specs(pspecs, opt)
    fn = steps.gnn_train_step(arch, cfg, opt_cfg)
    args = (params, opt, batch)
    in_sh = (_to_ns(mesh, pspecs), _to_ns(mesh, ospecs), _to_ns(mesh, bspec))
    out_sh = (_to_ns(mesh, pspecs), _to_ns(mesh, ospecs), None)
    if arch == "dimenet":
        flops = 3.0 * _GNN_FWD_FLOPS[arch](cfg, n, e_slots, trip or 0)
    else:
        flops = 3.0 * _GNN_FWD_FLOPS[arch](cfg, n, e_slots)
    return Cell(arch, shape_name, "train", fn, args, in_sh, out_sh, flops)


# ------------------------------------------------------------------- BST

def _bst_cell(cfg, shape_name: str, mesh: Mesh, opt_cfg: OptConfig) -> Cell:
    from repro.configs.recsys import RECSYS_SHAPES

    info = RECSYS_SHAPES[shape_name]
    kind = info["kind"]
    d_axes = sh.data_axes(mesh)
    flat = sh.flat_axes(mesh)
    params = _eval_params("bst", cfg)
    pspecs = sh.bst_param_specs(params, mesh)
    d = cfg.embed_dim
    seq_flops = cfg.n_blocks * (
        8 * cfg.seq_len * d * d + 4 * cfg.seq_len ** 2 * d
    ) + 2 * sum(
        a * b for a, b in zip(
            (cfg.seq_len * d + d,) + cfg.mlp_dims, cfg.mlp_dims + (1,)
        )
    )
    if kind == "train":
        b = info["batch"]
        opt = jax.eval_shape(lambda p: opt_init(opt_cfg, p), params)
        ospecs = sh.opt_state_specs(pspecs, opt)
        fn = steps.bst_train_step(cfg, opt_cfg)
        args = (
            params, opt,
            _sds((b, cfg.seq_len - 1), jnp.int32), _sds((b,), jnp.int32),
            _sds((b * cfg.profile_bag,), jnp.int32),
            _sds((b * cfg.profile_bag,), jnp.int32), _sds((b,), jnp.float32),
        )
        in_sh = (
            _to_ns(mesh, pspecs), _to_ns(mesh, ospecs),
            NamedSharding(mesh, P(d_axes, None)),
            NamedSharding(mesh, P(d_axes)), NamedSharding(mesh, P(d_axes)),
            NamedSharding(mesh, P(d_axes)), NamedSharding(mesh, P(d_axes)),
        )
        out_sh = (_to_ns(mesh, pspecs), _to_ns(mesh, ospecs), None)
        flops = 3.0 * b * seq_flops
    elif kind == "serve":
        b = info["batch"]
        fn = steps.bst_serve_step(cfg)
        args = (
            params, _sds((b, cfg.seq_len - 1), jnp.int32),
            _sds((b,), jnp.int32), _sds((b * cfg.profile_bag,), jnp.int32),
            _sds((b * cfg.profile_bag,), jnp.int32),
        )
        in_sh = (
            _to_ns(mesh, pspecs), NamedSharding(mesh, P(d_axes, None)),
            NamedSharding(mesh, P(d_axes)), NamedSharding(mesh, P(d_axes)),
            NamedSharding(mesh, P(d_axes)),
        )
        out_sh = None
        flops = 1.0 * b * seq_flops
    else:  # retrieval
        # pad candidate count to a 512-multiple so the flat axis divides it
        # on both production meshes (scores of pad slots are discarded)
        c = -(-info["n_candidates"] // 512) * 512
        fn = steps.bst_retrieval_step(cfg)
        args = (
            params, _sds((cfg.seq_len - 1,), jnp.int32), _sds((c,), jnp.int32),
        )
        in_sh = (
            _to_ns(mesh, pspecs), NamedSharding(mesh, P()),
            NamedSharding(mesh, P(flat)),
        )
        out_sh = NamedSharding(mesh, P(flat))
        flops = 1.0 * c * seq_flops
    return Cell("bst", shape_name, kind, fn, args, in_sh, out_sh, flops)


# ------------------------------------------------------------------- TC

def _tc_cell(cfg: dict, shape_name: str, mesh: Mesh) -> Cell:
    from repro.configs.cover_edge_tc import SHAPES
    from repro.core.parallel_tc import build_tc_shard_fn, result_out_specs

    info = {**cfg, **SHAPES[shape_name]}  # shape owns scale/edge_factor
    info.update({k: v for k, v in cfg.items()
                 if k not in ("scale", "edge_factor", "name")})
    scale, ef = info["scale"], info["edge_factor"]
    n = 1 << scale
    m2 = 2 * ef * n
    # the paper's p processors = a flat 1-D re-view of the same devices
    p = mesh.devices.size
    tc_mesh = Mesh(mesh.devices.reshape(-1), ("p",))
    fn_shard, cap_edges = build_tc_shard_fn(
        n=n, m2=m2, p=p, axis_name="p",
        d_pad=info.get("d_pad", 256),
        mode=info.get("mode", "ring"),
        hedge_chunk=info.get("hedge_chunk", 4096),
        slack=info.get("slack", 4.0),
        frontier_dtype=info.get("frontier_dtype", "int32"),
    )
    fn = jax.shard_map(
        fn_shard, mesh=tc_mesh, in_specs=(P("p"), P("p")),
        out_specs=result_out_specs("p"),
    )
    args = (
        _sds((p * cap_edges,), jnp.int32), _sds((p * cap_edges,), jnp.int32),
    )
    in_sh = (NamedSharding(tc_mesh, P("p")), NamedSharding(tc_mesh, P("p")))
    # "useful work": one compare per probe, k·m·d̄ probes (k≈0.65, d̄=2·ef)
    flops = 0.65 * (m2 / 2) * (2 * ef) * math.log2(max(cap_edges, 2))
    return Cell("cover-edge-tc", shape_name, "tc", fn, args, in_sh, None,
                flops, mesh=tc_mesh)


# ------------------------------------------------------------------- api

def build_cell(arch: str, shape: str, mesh: Mesh, *,
               opt_cfg: OptConfig | None = None, smoke: bool = False,
               overrides: dict | None = None) -> Cell:
    """``overrides``: dataclass-field tweaks applied to the arch config —
    the §Perf hillclimb knobs (e.g. {"attn_impl": "chunked",
    "act_dtype": "bfloat16"}).  Nested MoE fields use "moe.<field>"."""
    mod = arch_module(arch)
    cfg = mod.SMOKE if smoke else mod.CONFIG
    if overrides:
        if isinstance(cfg, dict):  # TC workload: plain dict knobs
            cfg = {**cfg, **overrides}
        else:
            moe_over = {k.split(".", 1)[1]: v for k, v in overrides.items()
                        if k.startswith("moe.")}
            flat_over = {k: v for k, v in overrides.items()
                         if not k.startswith("moe.")}
            if moe_over and getattr(cfg, "moe", None) is not None:
                flat_over["moe"] = dataclasses.replace(cfg.moe, **moe_over)
            cfg = dataclasses.replace(cfg, **flat_over)
    opt_cfg = opt_cfg or OptConfig()
    if mod.FAMILY == "lm":
        return _lm_cell(arch, cfg, shape, mesh, opt_cfg)
    if mod.FAMILY == "gnn":
        return _gnn_cell(arch, cfg, shape, mesh, opt_cfg)
    if mod.FAMILY == "recsys":
        return _bst_cell(cfg, shape, mesh, opt_cfg)
    if mod.FAMILY == "tc":
        return _tc_cell(cfg, shape, mesh)
    raise ValueError(arch)


def opt_overrides(arch: str) -> dict:
    """The §Perf-winning execution knobs per arch (math-preserving)."""
    from repro.configs.lm import OPT, OPT_MOE

    mod = arch_module(arch)
    if mod.FAMILY == "lm":
        return dict(OPT_MOE if getattr(mod.CONFIG, "moe", None) else OPT)
    if mod.FAMILY == "tc":
        # d_pad=64 is safe at p>=256 (max sublist ~ d_max/p; overflow flag
        # guards production runs — see EXPERIMENTS.md §Perf TC iteration 2)
        return dict(frontier_dtype="uint8", slack=2.0, d_pad=64)
    return {}


def all_cells() -> list[tuple[str, str]]:
    out = []
    for arch in ASSIGNED_ARCHS:
        for shape in arch_module(arch).SHAPES:
            out.append((arch, shape))
    return out
