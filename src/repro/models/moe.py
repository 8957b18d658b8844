"""Mixture-of-Experts FFN: top-k routing with sort-based capacity dispatch.

Scalable dispatch (MegaBlocks/MaxText-style), NOT the (S, E, C) one-hot
einsum — that dispatch tensor is O(S²·cf/E) and detonates at 32k-sequence
shapes.  Here:

  1. top-k(router logits) -> (token, expert, gate) triples;
  2. sort triples by expert; position-within-expert via a searchsorted
     subtraction; entries beyond per-expert capacity are dropped
     (classic capacity-factor semantics);
  3. scatter token activations into an [E, C, D] buffer -> batched expert
     GEMMs ``ecd,edf->ecf`` (MXU-dense even when experts are ragged);
  4. combine with the gathered gate weights.

Expert parallelism: the [E, C, D] buffer carries a sharding constraint on
E (mesh 'model' axis); GSPMD turns the scatter/gather into the expert
all_to_all.  Shared experts (qwen2-moe) are a plain dense GLU branch.
Aux load-balance loss is the Switch/GShard fraction-product.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init, glu_mlp, glu_mlp_init


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff_expert: int
    d_ff_shared: int = 0       # qwen2-moe: 4 shared experts == one 4x GLU
    capacity_factor: float = 1.25
    router_dtype: str = "float32"
    # physical expert count padded for expert-parallel divisibility (e.g.
    # qwen2's 60 routed experts -> 64 slots on a 16-way model axis); the
    # router masks the dummy slots to -inf so semantics stay at n_experts.
    pad_experts_to: int = 0
    # "gspmd" (sort-based dispatch, partitioner inserts collectives) or
    # "a2a" (explicit shard_map all_to_all expert parallelism — §Perf)
    dispatch: str = "gspmd"

    @property
    def n_phys(self) -> int:
        return max(self.n_experts, self.pad_experts_to)

    def param_count(self, d_model: int) -> int:
        p = self.n_experts * 3 * d_model * self.d_ff_expert
        p += d_model * self.n_experts  # router
        if self.d_ff_shared:
            p += 3 * d_model * self.d_ff_shared
        return p

    def active_param_count(self, d_model: int) -> int:
        p = self.top_k * 3 * d_model * self.d_ff_expert
        p += d_model * self.n_experts
        if self.d_ff_shared:
            p += 3 * d_model * self.d_ff_shared
        return p


def moe_ffn_init(key, cfg: MoEConfig, d_model: int, dtype=jnp.float32):
    ks = jax.random.split(key, 5)
    e, f = cfg.n_phys, cfg.d_ff_expert

    def one(k):
        k1, k2, k3 = jax.random.split(k, 3)
        return {
            "w_gate": dense_init(k1, d_model, f, dtype),
            "w_up": dense_init(k2, d_model, f, dtype),
            "w_down": dense_init(k3, f, d_model, dtype),
        }

    params = {
        "router": dense_init(ks[0], d_model, e, dtype),
        "experts": jax.vmap(one)(jax.random.split(ks[1], e)),  # [E, ...]
    }
    if cfg.d_ff_shared:
        params["shared"] = glu_mlp_init(ks[2], d_model, cfg.d_ff_shared, dtype)
    return params


def moe_ffn(params, cfg: MoEConfig, x, *, capacity: Optional[int] = None):
    """x [B, S, D] -> (out [B, S, D], aux_loss scalar)."""
    if cfg.dispatch == "a2a":
        from repro.models.moe_a2a import a2a_applicable, moe_ffn_a2a

        mesh = jax.sharding.get_abstract_mesh()
        if a2a_applicable(cfg, x, mesh):
            return moe_ffn_a2a(params, cfg, x)
    b, s, d = x.shape
    tokens = x.reshape(b * s, d)
    n_tok = b * s
    e, k = cfg.n_phys, cfg.top_k
    if capacity is None:
        capacity = max(1, int(n_tok * k * cfg.capacity_factor / cfg.n_experts))

    logits = (tokens @ params["router"]).astype(jnp.dtype(cfg.router_dtype))
    if cfg.n_phys > cfg.n_experts:  # mask padded expert slots
        pad_mask = jnp.arange(e) >= cfg.n_experts
        logits = jnp.where(pad_mask[None, :], -1e30, logits)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)  # [T, k]
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9
    )

    # ---- aux load-balance loss (computed pre-drop, Switch style)
    frac_routed = jnp.mean(
        jax.nn.one_hot(expert_idx, e, dtype=jnp.float32), axis=(0, 1)
    )
    frac_prob = jnp.mean(probs.astype(jnp.float32), axis=0)
    aux = e * jnp.sum(frac_routed * frac_prob)

    # ---- sort-based dispatch
    flat_expert = expert_idx.reshape(-1)          # [T*k]
    flat_token = (
        jnp.broadcast_to(jnp.arange(n_tok)[:, None], (n_tok, k)).reshape(-1)
    )
    flat_gate = gate_vals.reshape(-1)
    order = jnp.argsort(flat_expert, stable=True)
    se, stok, sgate = flat_expert[order], flat_token[order], flat_gate[order]
    starts = jnp.searchsorted(se, jnp.arange(e)).astype(jnp.int32)
    pos = jnp.arange(n_tok * k, dtype=jnp.int32) - starts[jnp.clip(se, 0, e - 1)]
    keep = pos < capacity
    # scatter into the expert buffer (dropped entries go out of range);
    # buffer sharded (experts='model', capacity='data') -> the scatter IS
    # the expert-parallel all_to_all under GSPMD
    from repro.distributed.constrain import maybe_constrain

    row = jnp.where(keep, se, e)
    col = jnp.where(keep, pos, 0)
    buf = jnp.zeros((e, capacity, d), tokens.dtype)
    buf = buf.at[row, col].set(tokens[stok], mode="drop")
    buf = maybe_constrain(buf, "model", ("pod", "data"), None)

    # ---- expert GEMMs (batched over E; sharded on E by the mesh rules)
    ex = params["experts"]
    h_gate = jnp.einsum("ecd,edf->ecf", buf, ex["w_gate"])
    h_up = jnp.einsum("ecd,edf->ecf", buf, ex["w_up"])
    h = jax.nn.silu(h_gate) * h_up
    y = jnp.einsum("ecf,efd->ecd", h, ex["w_down"])

    # ---- combine
    gathered = y.at[row, col].get(mode="fill", fill_value=0.0)  # [T*k, D]
    combined = jax.ops.segment_sum(
        gathered * jnp.where(keep, sgate, 0.0)[:, None].astype(y.dtype),
        stok,
        num_segments=n_tok,
    )
    out = combined.reshape(b, s, d)
    if cfg.d_ff_shared:
        out = out + glu_mlp(params["shared"], x, act="silu")
    return out, aux
