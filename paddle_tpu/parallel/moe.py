"""Expert parallelism: Switch-style Mixture-of-Experts over a mesh axis.

The reference has no MoE (SURVEY §3 marks EP absent); this implements the
TPU-native design directly — top-1 routing with **sort-based dispatch**
(the MaxText/Praxis formulation): tokens are argsorted by their chosen
expert and scattered into capacity-packed per-expert buffers, so dispatch
memory is O(N·D + E·C·D) instead of the GShard one-hot formulation's
O(N·E·C) dispatch tensor (which dominates at real expert counts). Experts
are sharded over an ``expert`` mesh axis inside ``shard_map`` with the
packed buffers exchanged over ICI. Everything is static-shape (capacity
padding, dropped-token masking) and differentiable.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


__all__ = ["switch_moe", "make_switch_ffn"]


def switch_moe(x, gate_w, expert_params, expert_fn: Callable, mesh: Mesh,
               axis: str = "expert", capacity_factor: float = 1.25):
    """Top-1 MoE layer, expert-parallel over ``axis``.

    - x [B, T, D] (replicated across the expert axis here; compose with a
      data axis for dp×ep)
    - gate_w [D, E]
    - expert_params: pytree with leading [E, ...] axis, sharded over ``axis``
      (each device holds its experts)
    - expert_fn(params_one_expert, tokens [C, D]) -> [C, D]

    Returns (y [B, T, D], aux_loss). Switch semantics: overflow tokens
    beyond an expert's capacity are dropped (pass through as zeros).
    Differentiable; only the capacity-packed [E, C, D] buffers move
    between experts.
    """
    b, t, d = x.shape
    n = b * t
    e = gate_w.shape[-1]
    n_shards = mesh.shape[axis]
    assert e % n_shards == 0, "experts must divide the expert axis"
    capacity = max(1, int(capacity_factor * n / e))

    flat = x.reshape(n, d)
    gate_logits = flat @ gate_w
    probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)
    expert = jnp.argmax(probs, axis=-1)                  # [N]
    gate = jnp.max(probs, axis=-1)                       # prob of chosen expert

    # sort-based dispatch: group tokens by expert, position within group
    order = jnp.argsort(expert)                          # stable
    sorted_expert = expert[order]
    counts = jnp.bincount(expert, length=e)              # [E]
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(n) - starts[sorted_expert]          # rank inside expert
    keep = pos < capacity
    # dropped tokens target a dummy row that is sliced off (zero cotangent)
    slot = jnp.where(keep, sorted_expert * capacity + pos, e * capacity)
    vals = flat[order] * keep[:, None].astype(x.dtype)
    buf = jnp.zeros((e * capacity + 1, d), x.dtype).at[slot].set(vals)
    expert_in = buf[:-1].reshape(e, capacity, d)

    def shard_body(params, buf_):
        # buf_ arrives [E/n_shards, C, D] for THIS shard's experts
        return jax.vmap(expert_fn)(params, buf_)

    expert_out = jax.shard_map(
        shard_body, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(axis), expert_params), P(axis)),
        out_specs=P(axis), check_vma=False,
    )(expert_params, expert_in)

    # combine: gather each token's expert output, weight by its gate prob
    out_flat = expert_out.reshape(e * capacity, d)
    safe_slot = jnp.clip(slot, 0, e * capacity - 1)
    gathered = out_flat[safe_slot] * keep[:, None].astype(x.dtype)
    y_sorted = gathered * (gate[order].astype(x.dtype))[:, None]
    inv = jnp.argsort(order)
    y = y_sorted[inv]

    # load-balancing auxiliary loss (Switch eq. 4): E * Σ_e f_e · p_e
    frac_tokens = counts.astype(jnp.float32) / n
    frac_probs = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(frac_tokens * frac_probs)
    return y.reshape(b, t, d).astype(x.dtype), aux.astype(x.dtype)


def make_switch_ffn(d_model: int, d_ff: int):
    """Standard per-expert FFN for switch_moe: params [E, ...] maker + fn."""

    def init(key, n_experts):
        k1, k2 = jax.random.split(key)
        s1 = (2.0 / (d_model + d_ff)) ** 0.5
        return {
            "w1": jax.random.normal(k1, (n_experts, d_model, d_ff)) * s1,
            "w2": jax.random.normal(k2, (n_experts, d_ff, d_model)) * s1,
        }

    def fn(p, tokens):
        return jax.nn.relu(tokens @ p["w1"]) @ p["w2"]

    return init, fn
