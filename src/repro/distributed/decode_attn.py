"""Flash-decode over a sequence-sharded KV cache (shard_map).

Each ``model``-axis shard holds a contiguous S/m slice of the KV cache.
For one new token:

  1. the shard owning position ``pos`` writes the fresh K/V into its local
     slice (conditional dynamic_update_slice — no cross-shard traffic);
  2. every shard computes *partial* attention over its slice: running
     (max m_i, denom l_i, weighted value o_i);
  3. the partials are merged with the standard log-sum-exp combine over a
     tiny ``all_gather`` / ``psum`` — bytes moved per layer per step are
     O(B·H·hd), independent of sequence length.

This is the TPU-idiomatic equivalent of flash-decode / paged KV on GPUs:
the 32k–524k KV never materializes on one chip, and the collective term of
the roofline stays flat in S (validated in EXPERIMENTS.md §Perf).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..models.layers import repeat_kv


def _partial_attention(q, k, v, valid):
    """Local partial softmax over raw (un-repeated) GQA KV.

    Returns (o [B,1,H,hd] f32, m, l [B,H,1])."""
    b, _one, h, hd = q.shape
    kvh = k.shape[2]
    g = h // kvh
    q5 = q.reshape(b, 1, kvh, g, hd).astype(jnp.float32)
    scores = jnp.einsum(
        "bqkgd,bskd->bkgqs", q5, k.astype(jnp.float32)
    ) / math.sqrt(hd)  # [B,KVH,G,1,S]
    scores = jnp.where(valid[:, None, None, None, :], scores, -1e30)
    m = scores.max(axis=-1)  # [B,KVH,G,1]
    p = jnp.exp(scores - m[..., None])
    l = p.sum(axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(jnp.float32))
    return o.reshape(b, 1, h, hd), m.reshape(b, h, 1), l.reshape(b, h, 1)


def make_gqa_flash_decode(mesh: Mesh, seq_axis: str = "model",
                          batch_spec: P | None = None):
    """Returns an attn impl: (q, k_new, v_new, k_cache, v_cache, pos) ->
    (out [B,1,H,hd], new_k_cache, new_v_cache) with caches S-sharded."""
    b_spec = batch_spec if batch_spec is not None else P(None)
    b_axis = b_spec[0] if len(b_spec) else None

    def impl(q, k_new, v_new, k_cache, v_cache, pos):
        num_heads = q.shape[2]

        def local(q, k_new, v_new, kc, vc, pos):
            idx = jax.lax.axis_index(seq_axis)
            s_local = kc.shape[1]
            offset = idx * s_local
            local_pos = pos - offset
            owns = (local_pos >= 0) & (local_pos < s_local)
            lp = jnp.clip(local_pos, 0, s_local - 1)
            # Slice-level select: non-owners re-write their existing row, so
            # only O(1 token) of cache traffic per shard (a whole-cache
            # jnp.where(owns, ...) here costs 3x full-cache HBM traffic).
            k_old = jax.lax.dynamic_slice(
                kc, (0, lp, 0, 0), (kc.shape[0], 1, kc.shape[2], kc.shape[3])
            )
            v_old = jax.lax.dynamic_slice(
                vc, (0, lp, 0, 0), (vc.shape[0], 1, vc.shape[2], vc.shape[3])
            )
            k_row = jnp.where(owns, k_new.astype(kc.dtype), k_old)
            v_row = jnp.where(owns, v_new.astype(vc.dtype), v_old)
            kc = jax.lax.dynamic_update_slice(kc, k_row, (0, lp, 0, 0))
            vc = jax.lax.dynamic_update_slice(vc, v_row, (0, lp, 0, 0))

            valid = (jnp.arange(s_local)[None, :] + offset) <= pos
            valid = jnp.broadcast_to(valid, (q.shape[0], s_local))
            o, m, l = _partial_attention(q, kc, vc, valid)

            # LSE combine across sequence shards (tiny tensors)
            g_m = jax.lax.pmax(m, seq_axis)
            scale = jnp.exp(m - g_m)
            l_tot = jax.lax.psum(l * scale, seq_axis)
            o_tot = jax.lax.psum(o * scale.transpose(0, 2, 1)[..., None], seq_axis)
            out = (o_tot / jnp.maximum(l_tot, 1e-30).transpose(0, 2, 1)[..., None])
            return out.astype(q.dtype), kc, vc

        return jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(
                P(b_axis, None, None, None),  # q replicated over model
                P(b_axis, None, None, None),
                P(b_axis, None, None, None),
                P(b_axis, seq_axis, None, None),
                P(b_axis, seq_axis, None, None),
                P(),
            ),
            out_specs=(
                P(b_axis, None, None, None),
                P(b_axis, seq_axis, None, None),
                P(b_axis, seq_axis, None, None),
            ),
            check_vma=False,
        )(q, k_new, v_new, k_cache, v_cache, pos)

    return impl


def make_mla_flash_decode(mesh: Mesh, seq_axis: str = "model",
                          batch_spec: P | None = None):
    """MLA absorbed flash-decode over an S-sharded compressed cache.

    (q_c [B,1,H,r], q_rope [B,1,H,rope], payload_new [B,1,r+rope],
     c_cache [B,S,r+rope], pos, r) -> (ctx [B,1,H,r], new_c_cache)
    where ctx is the attention read in compressed space (caller applies the
    absorbed value up-projection).
    """
    b_spec = batch_spec if batch_spec is not None else P(None)
    b_axis = b_spec[0] if len(b_spec) else None

    def impl(q_c, q_rope, payload_new, c_cache, pos, r, scale_dim):
        def local(q_c, q_rope, payload_new, cc, pos):
            idx = jax.lax.axis_index(seq_axis)
            s_local = cc.shape[1]
            offset = idx * s_local
            local_pos = pos - offset
            owns = (local_pos >= 0) & (local_pos < s_local)
            lp = jnp.clip(local_pos, 0, s_local - 1)
            old = jax.lax.dynamic_slice(
                cc, (0, lp, 0), (cc.shape[0], 1, cc.shape[2])
            )
            row = jnp.where(owns, payload_new.astype(cc.dtype), old)
            cc = jax.lax.dynamic_update_slice(cc, row, (0, lp, 0))

            c_kv = cc[..., :r].astype(jnp.float32)
            k_rope = cc[..., r:].astype(jnp.float32)
            scores = (
                jnp.einsum("bqhr,bsr->bhqs", q_c.astype(jnp.float32), c_kv)
                + jnp.einsum("bqhn,bsn->bhqs", q_rope.astype(jnp.float32), k_rope)
            ) / math.sqrt(scale_dim)
            valid = (jnp.arange(s_local)[None, :] + offset) <= pos
            scores = jnp.where(valid[:, None, None, :], scores, -1e30)
            m = scores.max(axis=-1)
            p = jnp.exp(scores - m[..., None])
            l = p.sum(axis=-1)
            ctx = jnp.einsum("bhqs,bsr->bqhr", p, c_kv)

            g_m = jax.lax.pmax(m, seq_axis)
            scale = jnp.exp(m - g_m)
            l_tot = jax.lax.psum(l * scale, seq_axis)
            ctx_tot = jax.lax.psum(ctx * scale.transpose(0, 2, 1)[..., None], seq_axis)
            ctx_out = ctx_tot / jnp.maximum(l_tot, 1e-30).transpose(0, 2, 1)[..., None]
            return ctx_out.astype(q_c.dtype), cc

        return jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(
                P(b_axis, None, None, None),
                P(b_axis, None, None, None),
                P(b_axis, None, None),
                P(b_axis, seq_axis, None),
                P(),
            ),
            out_specs=(
                P(b_axis, None, None, None),
                P(b_axis, seq_axis, None),
            ),
            check_vma=False,
        )(q_c, q_rope, payload_new, c_cache, pos)

    return impl
