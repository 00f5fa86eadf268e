"""Mixture-of-Experts FFN with capacity-factor scatter/gather dispatch.

Token-choice top-k routing (Qwen3-MoE, DeepSeekMoE).  Dispatch builds a
[B, E, C, D] buffer per batch row via scatter-add; expert matmuls are a
batched einsum with the expert axis sharded over the ``model`` mesh axis;
combine gathers results back and weighs by the (optionally renormalized)
gates.  Tokens over capacity are dropped (standard capacity-factor
semantics) — the capacity factor bounds the buffer so the whole block stays
static-shaped for XLA/GSPMD.

DeepSeek's *shared experts* are dense MLPs added unconditionally.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..distributed.act_sharding import constrain
from .config import ModelConfig
from .layers import PARAM_DTYPE


def moe_capacity(cfg: ModelConfig, seq_len: int) -> int:
    cap = int(
        math.ceil(seq_len * cfg.moe_top_k / cfg.moe_num_experts * cfg.moe_capacity_factor)
    )
    return max(8, -(-cap // 8) * 8)  # round up to 8 for lane alignment


def init_moe_params(cfg: ModelConfig, key: jax.Array) -> dict:
    d, f, e = cfg.d_model, cfg.moe_d_ff, cfg.moe_num_experts
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    p = {
        "router": jax.random.normal(k1, (d, e), jnp.float32) / math.sqrt(d),
        "w_gate": jax.random.normal(k2, (e, d, f), PARAM_DTYPE) / math.sqrt(d),
        "w_up": jax.random.normal(k3, (e, d, f), PARAM_DTYPE) / math.sqrt(d),
        "w_down": jax.random.normal(k4, (e, f, d), PARAM_DTYPE) / math.sqrt(f),
    }
    if cfg.moe_num_shared:
        fs = f * cfg.moe_num_shared
        ks = jax.random.split(k5, 3)
        p["shared"] = {
            "w_gate": jax.random.normal(ks[0], (d, fs), PARAM_DTYPE) / math.sqrt(d),
            "w_up": jax.random.normal(ks[1], (d, fs), PARAM_DTYPE) / math.sqrt(d),
            "w_down": jax.random.normal(ks[2], (fs, d), PARAM_DTYPE) / math.sqrt(fs),
        }
    return p


def moe_block(cfg: ModelConfig, p: dict, x: jnp.ndarray) -> jnp.ndarray:
    """Dispatch: expert-parallel shard_map when a mesh policy with a
    ``model`` axis is installed and experts divide it; otherwise the dense
    scatter formulation (single-host tests, and the GSPMD baseline the perf
    log compares against — see EXPERIMENTS.md §Perf)."""
    from ..distributed import act_sharding

    pol = act_sharding._policy()
    if pol is not None and pol.get("moe_impl", "shard_map") == "shard_map":
        mesh = pol["mesh"]
        m = mesh.shape.get("model", 1)
        if m > 1 and cfg.moe_num_experts % m == 0:
            return _moe_block_shard_map(cfg, p, x, pol)
    return _moe_block_dense(cfg, p, x)


def _moe_block_dense(cfg: ModelConfig, p: dict, x: jnp.ndarray) -> jnp.ndarray:
    """x: [B, S, D] -> [B, S, D]."""
    b, s, d = x.shape
    e, k = cfg.moe_num_experts, cfg.moe_top_k
    c = moe_capacity(cfg, s)

    logits = (x.astype(jnp.float32) @ p["router"]).astype(jnp.float32)  # [B,S,E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, k)  # [B,S,k]
    if cfg.moe_norm_topk:
        gate = gate / jnp.maximum(gate.sum(axis=-1, keepdims=True), 1e-9)

    sk = s * k
    e_flat = idx.reshape(b, sk)  # expert of each slot
    # position of each slot within its expert's buffer (per batch row)
    onehot = jax.nn.one_hot(e_flat, e, dtype=jnp.int32)  # [B, sk, E]
    pos_all = jnp.cumsum(onehot, axis=1) - 1  # [B, sk, E]
    pos = jnp.take_along_axis(pos_all, e_flat[..., None], axis=-1)[..., 0]  # [B, sk]
    in_cap = pos < c

    tok_of_slot = jnp.arange(sk) // k  # [sk]
    src = jnp.take(x, tok_of_slot, axis=1)  # [B, sk, D]
    src = src * in_cap[..., None].astype(x.dtype)
    pos_c = jnp.where(in_cap, pos, c - 1)

    batch_idx = jnp.broadcast_to(jnp.arange(b)[:, None], (b, sk))
    buffer = constrain(jnp.zeros((b, e, c, d), x.dtype), "batch", "model")
    buffer = buffer.at[batch_idx, e_flat, pos_c].add(src, mode="drop")
    buffer = constrain(buffer, "batch", "model")

    # Expert MLPs: expert axis is a batched matmul dim (sharded on `model`).
    h = jax.nn.silu(
        jnp.einsum("becd,edf->becf", buffer, p["w_gate"])
    ) * jnp.einsum("becd,edf->becf", buffer, p["w_up"])
    out_buf = jnp.einsum("becf,efd->becd", h, p["w_down"])  # [B,E,C,D]

    # Combine: gather each slot's result, weight by gate, sum over k.
    gathered = out_buf[batch_idx, e_flat, pos_c]  # [B, sk, D]
    gathered = gathered * (gate.reshape(b, sk, 1) * in_cap[..., None]).astype(x.dtype)
    out = gathered.reshape(b, s, k, d).sum(axis=2)

    if cfg.moe_num_shared:
        sp = p["shared"]
        out = out + (jax.nn.silu(x @ sp["w_gate"]) * (x @ sp["w_up"])) @ sp["w_down"]
    return out


# ---------------------------------------------------------------------------
# Expert-parallel shard_map implementation
# ---------------------------------------------------------------------------
#
# Experts are sharded over the ``model`` mesh axis; activations are sharded
# over ``data`` and REPLICATED over ``model``.  Each model-rank dispatches
# tokens to its local experts with a purely local scatter (zero dispatch
# collectives — the tokens are already present), computes its expert MLPs,
# scatters results back, and a single psum over ``model`` combines partial
# outputs.  Collective bytes per MoE layer = one [B_local, S, D] psum —
# the same order as a Megatron-style TP FFN, vs. the GSPMD scatter
# formulation's per-layer buffer all-gathers (measured 600x worse in the
# dry-run; see EXPERIMENTS.md §Perf).


def _moe_local_compute(cfg: ModelConfig, x_l, router, w_gate, w_up, w_down, e0):
    """Token dispatch + expert MLPs for the local expert range [e0, e0+E_l)."""
    b, s, d = x_l.shape
    e, k = cfg.moe_num_experts, cfg.moe_top_k
    e_l = w_gate.shape[0]
    c = moe_capacity(cfg, s)

    logits = (x_l.astype(jnp.float32) @ router)  # [B,S,E] (replicated compute)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, k)  # [B,S,k]
    if cfg.moe_norm_topk:
        gate = gate / jnp.maximum(gate.sum(axis=-1, keepdims=True), 1e-9)

    sk = s * k
    e_flat = idx.reshape(b, sk)
    # capacity position must match the global (dense) semantics: rank within
    # the expert across the whole row, computed over ALL experts
    onehot = jax.nn.one_hot(e_flat, e, dtype=jnp.int32)
    pos_all = jnp.cumsum(onehot, axis=1) - 1
    pos = jnp.take_along_axis(pos_all, e_flat[..., None], axis=-1)[..., 0]
    in_cap = pos < c

    local = (e_flat >= e0) & (e_flat < e0 + e_l) & in_cap
    e_local = jnp.clip(e_flat - e0, 0, e_l - 1)
    pos_c = jnp.where(local, pos, c - 1)

    tok_of_slot = jnp.arange(sk) // k
    src = jnp.take(x_l, tok_of_slot, axis=1) * local[..., None].astype(x_l.dtype)
    batch_idx = jnp.broadcast_to(jnp.arange(b)[:, None], (b, sk))
    buffer = jnp.zeros((b, e_l, c, d), x_l.dtype)
    buffer = buffer.at[batch_idx, e_local, pos_c].add(src, mode="drop")

    h = jax.nn.silu(
        jnp.einsum("becd,edf->becf", buffer, w_gate)
    ) * jnp.einsum("becd,edf->becf", buffer, w_up)
    out_buf = jnp.einsum("becf,efd->becd", h, w_down)

    gathered = out_buf[batch_idx, e_local, pos_c]
    gathered = gathered * (gate.reshape(b, sk, 1) * local[..., None]).astype(x_l.dtype)
    return gathered.reshape(b, s, k, d).sum(axis=2)


def _moe_block_shard_map(cfg: ModelConfig, p: dict, x: jnp.ndarray, pol) -> jnp.ndarray:
    from jax.sharding import PartitionSpec as P

    mesh = pol["mesh"]
    batch_axes = pol.get("batch")
    b_axis = None
    if batch_axes and x.shape[0] % _mesh_axes_size(mesh, batch_axes) == 0:
        b_axis = tuple(batch_axes) if len(batch_axes) > 1 else batch_axes[0]

    def local(x_l, router, w_gate, w_up, w_down):
        e_l = w_gate.shape[0]
        e0 = jax.lax.axis_index("model") * e_l
        out_partial = _moe_local_compute(cfg, x_l, router, w_gate, w_up, w_down, e0)
        return jax.lax.psum(out_partial, "model")

    out = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            P(b_axis, None, None),
            P(None, None),
            P("model", None, None),
            P("model", None, None),
            P("model", None, None),
        ),
        out_specs=P(b_axis, None, None),
        check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])

    if cfg.moe_num_shared:
        sp = p["shared"]
        out = out + (jax.nn.silu(x @ sp["w_gate"]) * (x @ sp["w_up"])) @ sp["w_down"]
    return out


def _mesh_axes_size(mesh, axes) -> int:
    total = 1
    for a in axes:
        total *= mesh.shape.get(a, 1)
    return total
