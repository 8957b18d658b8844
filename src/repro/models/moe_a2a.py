"""Expert-parallel MoE dispatch with EXPLICIT all_to_all (shard_map).

§Perf iteration for the collective-bound MoE cells: the pjit/GSPMD
lowering of the sort-based dispatch (moe.py) scatters into / gathers from
a globally-sharded [E, C, D] buffer, which XLA realizes as repeated
activation-sized all-gathers.  The known-good MoE pattern (GShard,
Switch, MaxText) instead:

  1. each model-peer takes its 1/n_model SLICE of the sequence (tokens are
     DP-sharded over data; the slice de-duplicates routing work across the
     TP axis),
  2. local top-k -> sort by expert -> send buffer [E_phys, C_send, D]
     with C_send = ceil(T_slice·k·cf / E),
  3. all_to_all over 'model': each peer receives its E/n_model experts'
     tokens from every peer -> [senders, E_loc, C_send, D],
  4. local expert GEMMs, reverse all_to_all, local gate-combine,
  5. the output returns S-sharded over 'model' (out_specs) — the residual
     add reassembles it (one all-gather, fused by the partitioner).

Wire bytes per device per layer ≈ 2 x T_slice·k·cf·D + T_slice·D — the
token-choice minimum — vs ~6-10x that in the GSPMD scatter lowering.

Capacity is per-(sender-slice, expert) — stricter than global capacity at
equal cf (aux loss keeps expected drop rates equal; documented deviation).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from repro.models.layers import glu_mlp


def _axes(mesh, names):
    return tuple(a for a in names if a in mesh.axis_names)


def a2a_applicable(cfg, x, mesh) -> bool:
    if "model" not in mesh.axis_names:
        return False
    n_model = mesh.shape["model"]
    return x.shape[1] % n_model == 0 and cfg.n_phys % n_model == 0


def moe_ffn_a2a(params, cfg, x):
    """x [B, S, D] (sharded (pod,data) on B) -> (out, aux)."""
    mesh = jax.sharding.get_abstract_mesh()
    model_ax = "model"
    data_axes = _axes(mesh, ("pod", "data"))
    n_model = mesh.shape[model_ax]
    e_phys = cfg.n_phys
    e_loc = e_phys // n_model

    def body(router_w, experts, shared, xl):
        # xl: [B_loc, S, D]; this peer dispatches S-slice [B_loc, S/n, D]
        b_loc, s, d = xl.shape
        s_loc = s // n_model
        my = jax.lax.axis_index(model_ax)
        xs = jax.lax.dynamic_slice_in_dim(xl, my * s_loc, s_loc, axis=1)
        t_loc = b_loc * s_loc
        tokens = xs.reshape(t_loc, d)
        k = cfg.top_k
        cap = max(1, int(t_loc * k * cfg.capacity_factor / cfg.n_experts))

        logits = (tokens @ router_w).astype(jnp.dtype(cfg.router_dtype))
        if cfg.n_phys > cfg.n_experts:
            pad = jnp.arange(e_phys) >= cfg.n_experts
            logits = jnp.where(pad[None, :], -1e30, logits)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_idx = jax.lax.top_k(probs, k)
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9
        )
        frac_routed = jnp.mean(
            jax.nn.one_hot(expert_idx, e_phys, dtype=jnp.float32), axis=(0, 1)
        )
        frac_prob = jnp.mean(probs.astype(jnp.float32), axis=0)
        stats = jax.lax.pmean(
            frac_routed * frac_prob, data_axes + (model_ax,)
        )
        aux = cfg.n_experts * jnp.sum(stats)

        flat_e = expert_idx.reshape(-1)
        flat_t = jnp.broadcast_to(
            jnp.arange(t_loc)[:, None], (t_loc, k)
        ).reshape(-1)
        flat_g = gate_vals.reshape(-1)
        order = jnp.argsort(flat_e, stable=True)
        se, stok, sgate = flat_e[order], flat_t[order], flat_g[order]
        starts = jnp.searchsorted(se, jnp.arange(e_phys)).astype(jnp.int32)
        pos = jnp.arange(t_loc * k, dtype=jnp.int32) - starts[
            jnp.clip(se, 0, e_phys - 1)
        ]
        keep = pos < cap
        row = jnp.where(keep, se, e_phys)
        col = jnp.where(keep, pos, 0)
        send = jnp.zeros((e_phys, cap, d), tokens.dtype)
        send = send.at[row, col].set(tokens[stok], mode="drop")

        # ---- dispatch all_to_all over the model axis ----------------
        send = send.reshape(n_model, e_loc, cap, d)
        recv = jax.lax.all_to_all(send, model_ax, 0, 0, tiled=True)
        grouped = recv.transpose(1, 0, 2, 3).reshape(e_loc, n_model * cap, d)

        h_gate = jnp.einsum("ecd,edf->ecf", grouped, experts["w_gate"])
        h_up = jnp.einsum("ecd,edf->ecf", grouped, experts["w_up"])
        y = jnp.einsum(
            "ecf,efd->ecd", jax.nn.silu(h_gate) * h_up, experts["w_down"]
        )

        # ---- combine: reverse all_to_all ----------------------------
        y = y.reshape(e_loc, n_model, cap, d).transpose(1, 0, 2, 3)
        back = jax.lax.all_to_all(y, model_ax, 0, 0, tiled=True)
        back = back.reshape(e_phys, cap, d)
        gathered = back.at[row, col].get(mode="fill", fill_value=0.0)
        combined = jax.ops.segment_sum(
            gathered * jnp.where(keep, sgate, 0.0)[:, None].astype(y.dtype),
            stok, num_segments=t_loc,
        )
        out = combined.reshape(b_loc, s_loc, d)
        if cfg.d_ff_shared:
            out = out + glu_mlp(shared, xs, act="silu")
        return out, aux

    experts_spec = {k_: P(model_ax, None, None)
                    for k_ in ("w_gate", "w_up", "w_down")}
    shared = params.get("shared")
    shared_spec = (
        jax.tree.map(lambda _: P(), shared) if shared is not None else None
    )
    out, aux = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(), experts_spec, shared_spec,
                  P(data_axes, None, None)),
        # out S-sharded over model; the residual add re-gathers it
        out_specs=(P(data_axes, model_ax, None), P()),
    )(params["router"], params["experts"], shared, x)
    return out, aux
