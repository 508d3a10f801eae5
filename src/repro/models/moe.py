"""Mixture-of-Experts with expert parallelism (sort-based capacity dispatch).

TPU-native formulation (see DESIGN.md §3): tokens stay replicated across
the `model` mesh axis inside the MoE block, experts are sharded over it.
Each shard dispatches only the tokens routed to ITS experts into a dense
(E_local, capacity, D) buffer (argsort + cumulative-rank, no (T, E, C)
one-hot tensor is ever built), runs the expert SwiGLUs as batched matmuls,
scatters weighted outputs back, and a single psum over the model axis
combines expert contributions — the same collective volume as a TP FFN.

Under pjit the block is wrapped in shard_map so the collective schedule is
explicit and auditable in the lowered HLO (the dry-run reads it).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["moe_ffn", "router_topk", "moe_ffn_sharded"]


def router_topk(logits: jnp.ndarray, top_k: int):
    """Softmax-then-top-k with renormalized combine weights.

    logits: (T, E) fp32. Returns (weights (T, K), experts (T, K) int32,
    aux_loss scalar) — aux is the standard load-balance term E * sum(f * P).
    """
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    weights, experts = jax.lax.top_k(probs, top_k)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    e = logits.shape[-1]
    # f_e: fraction of tokens whose top-1 hits e; P_e: mean router prob.
    top1 = experts[:, 0]
    f = jnp.bincount(top1, length=e) / top1.shape[0]
    p_mean = probs.mean(0)
    aux = e * jnp.sum(f * p_mean)
    return weights, experts, aux


def _dispatch_combine(
    x: jnp.ndarray,  # (T, D)
    weights: jnp.ndarray,  # (T, K)
    experts: jnp.ndarray,  # (T, K) global expert ids
    w_gate: jnp.ndarray,  # (E_loc, D, F)
    w_up: jnp.ndarray,
    w_down: jnp.ndarray,  # (E_loc, F, D)
    e_start: int,
    capacity: int,
) -> jnp.ndarray:
    t, d = x.shape
    k = weights.shape[1]
    e_loc = w_gate.shape[0]

    flat_e = experts.reshape(-1) - e_start  # (T*K,) local expert index
    flat_w = weights.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(t), k)
    local = (flat_e >= 0) & (flat_e < e_loc)
    # Non-local pairs sort to a sentinel bucket past the real experts.
    sort_key = jnp.where(local, flat_e, e_loc)
    order = jnp.argsort(sort_key, stable=True)
    se, st, sw = sort_key[order], flat_t[order], flat_w[order]
    # Rank within each expert via one-hot cumsum over E_loc lanes (cheap:
    # T*K x E_loc, with E_loc = E / model_parallelism).
    onehot = jax.nn.one_hot(se, e_loc, dtype=jnp.int32)
    prior = jnp.cumsum(onehot, axis=0) - onehot  # prior count per expert
    rank = jnp.take_along_axis(prior, jnp.minimum(se, e_loc - 1)[:, None], axis=1)[:, 0]
    keep = (se < e_loc) & (rank < capacity)
    slot = jnp.where(keep, se * capacity + rank, e_loc * capacity)  # overflow slot

    buf = jnp.zeros((e_loc * capacity + 1, d), x.dtype).at[slot].set(
        jnp.where(keep[:, None], x[st], 0))
    buf = buf[:-1].reshape(e_loc, capacity, d)

    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w_gate))
    u = jnp.einsum("ecd,edf->ecf", buf, w_up)
    y = jnp.einsum("ecf,efd->ecd", g * u, w_down)  # (E_loc, C, D)

    y_flat = jnp.concatenate([y.reshape(e_loc * capacity, d),
                              jnp.zeros((1, d), y.dtype)])
    gathered = y_flat[slot] * sw[:, None].astype(y.dtype)  # (T*K, D)
    out = jnp.zeros((t, d), y.dtype).at[st].add(jnp.where(keep[:, None], gathered, 0))
    return out


def moe_ffn(
    x: jnp.ndarray,  # (B, S, D) or (T, D)
    p: dict,  # router (D, E); w_gate/w_up (E, D, F); w_down (E, F, D)
    top_k: int,
    capacity_factor: float = 1.25,
    e_start: int = 0,
    num_experts_global: int | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Single-shard MoE. Returns (out, aux_loss)."""
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    t = x2.shape[0]
    e_glob = num_experts_global or p["w_gate"].shape[0]
    logits = jnp.einsum("td,de->te", x2, p["router"].astype(x2.dtype))
    weights, experts, aux = router_topk(logits, top_k)
    # Floor of top_k*2 keeps tiny decode batches drop-free (a dropped token
    # at serve time would silently change the served distribution).
    capacity = max(int(capacity_factor * t * top_k / e_glob), 2 * top_k)
    out = _dispatch_combine(x2, weights.astype(x2.dtype), experts,
                            p["w_gate"], p["w_up"], p["w_down"],
                            e_start, capacity)
    return out.reshape(shape), aux


def moe_ffn_sharded(
    x: jnp.ndarray,  # (B, S, D)
    p: dict,
    cfg,
    mesh: jax.sharding.Mesh,
    batch_axes: tuple[str, ...],
    expert_axis: str = "model",
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Expert-parallel MoE via shard_map (see module docstring)."""
    n_shards = mesh.shape[expert_axis]
    e_glob = cfg.num_experts
    assert e_glob % n_shards == 0, (e_glob, n_shards)

    def local(x_l, router, wg, wu, wd):
        idx = jax.lax.axis_index(expert_axis)
        e_loc = wg.shape[0]
        out, aux = moe_ffn(
            x_l, {"router": router, "w_gate": wg, "w_up": wu, "w_down": wd},
            cfg.top_k, cfg.capacity_factor,
            e_start=idx * e_loc, num_experts_global=e_glob,
        )
        out = jax.lax.psum(out, expert_axis)
        aux = jax.lax.pmean(aux, expert_axis)
        if batch_axes:
            aux = jax.lax.pmean(aux, batch_axes)
        return out, aux

    x_spec = P(batch_axes if batch_axes else None, None, None)
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(x_spec,
                  P(None, None),  # router replicated
                  P(expert_axis, None, None),
                  P(expert_axis, None, None),
                  P(expert_axis, None, None)),
        out_specs=(x_spec, P()),
        check_vma=False,
    )
    return fn(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
