"""Distributed vector search: Manu's segment-parallel two-phase reduce as a
``shard_map`` over the device mesh.

The paper's scale-out (§3.6): segments are distributed over query nodes;
each node computes segment-wise top-k, merges to node-wise top-k, and the
proxy aggregates the global top-k.  On a TPU mesh this maps to: base
vectors row-sharded over every device, each device scans its shard (MXU
distance kernel), and the reduce is an ``all_gather`` of k-sized partials
(+ final local sort) — bytes moved are O(devices * k), independent of
collection size.

Runs identically on 2 host devices (tests) and the 256-chip production
mesh (dry-run); ``dryrun_search`` lowers + compiles it for the roofline.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P


def _local_topk(queries, base_shard, k, metric, row_offset, valid=None):
    q = queries.astype(jnp.float32)
    x = base_shard.astype(jnp.float32)
    # full f32 on the MXU, as in the l2_topk kernel this is checked against
    qx = jnp.dot(q, x.T, precision=jax.lax.Precision.HIGHEST)
    if metric == "l2":
        scores = (
            jnp.sum(q * q, axis=1, keepdims=True)
            - 2.0 * qx
            + jnp.sum(x * x, axis=1)[None, :]
        )
        scores = -scores  # top_k takes max
    else:
        scores = qx
    if valid is not None:
        scores = jnp.where(valid[None, :] > 0, scores, -jnp.inf)
    vals, idx = jax.lax.top_k(scores, k)
    return vals, idx + row_offset


def make_distributed_search(mesh: Mesh, k: int, metric: str = "l2"):
    """Returns search(queries [NQ,D] replicated, base [N,D] row-sharded,
    valid [N]) -> (scores [NQ,k], global row idx [NQ,k]).

    Base rows are sharded over ALL mesh axes (maximum scan parallelism —
    the 'segments spread over every query node' configuration).
    """
    axes = tuple(mesh.axis_names)
    n_shards = int(np.prod([mesh.shape[a] for a in axes]))

    def search(queries, base, valid):
        def local(q, x_shard, v_shard):
            # flatten the multi-axis shard index into a row offset
            shard_idx = jax.lax.axis_index(axes)
            rows_local = x_shard.shape[0]
            offset = shard_idx * rows_local
            k_local = min(k, rows_local)
            vals, idx = _local_topk(q, x_shard, k_local, metric, offset, v_shard)
            # two-phase reduce: one all_gather of the k-sized partials over
            # every mesh axis (O(devices*k) bytes), then a local re-reduce.
            all_vals = jax.lax.all_gather(vals, axes, axis=0, tiled=False)
            all_idx = jax.lax.all_gather(idx, axes, axis=0, tiled=False)
            nq = q.shape[0]
            cand_v = jnp.moveaxis(all_vals.reshape(-1, nq, k_local), 0, 1).reshape(nq, -1)
            cand_i = jnp.moveaxis(all_idx.reshape(-1, nq, k_local), 0, 1).reshape(nq, -1)
            out_v, sel = jax.lax.top_k(cand_v, k)
            out_i = jnp.take_along_axis(cand_i, sel, axis=1)
            return out_v, out_i

        out = jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(P(None, None), P(axes, None), P(axes)),
            out_specs=(P(), P()),
            check_vma=False,
        )(queries, base, valid)
        vals, idx = out
        if metric == "l2":
            vals = -vals
        return vals, idx

    return search


def distributed_search_host(queries, base, k, metric="l2", mesh=None):
    """Convenience wrapper: shards base over available devices and runs."""
    mesh = mesh or jax.make_mesh(
        (jax.device_count(),), ("data",), axis_types=(AxisType.Auto,)
    )
    n = base.shape[0]
    n_dev = mesh.devices.size
    pad = (-n) % n_dev
    basep = np.pad(base, ((0, pad), (0, 0)))
    valid = np.concatenate([np.ones(n, np.int32), np.zeros(pad, np.int32)])
    fn = make_distributed_search(mesh, k, metric)
    with mesh:
        base_sh = jax.device_put(basep, NamedSharding(mesh, P(tuple(mesh.axis_names), None)))
        valid_sh = jax.device_put(valid, NamedSharding(mesh, P(tuple(mesh.axis_names))))
        q_sh = jax.device_put(np.asarray(queries, np.float32), NamedSharding(mesh, P(None, None)))
        vals, idx = jax.jit(fn)(q_sh, base_sh, valid_sh)
    return np.asarray(vals), np.asarray(idx)


def dryrun_search(mesh: Mesh, n_rows: int, dim: int, nq: int, k: int, metric="l2"):
    """Lower + compile the distributed search at production-mesh scale."""
    fn = make_distributed_search(mesh, k, metric)
    axes = tuple(mesh.axis_names)
    with mesh:
        lowered = jax.jit(fn).lower(
            jax.ShapeDtypeStruct((nq, dim), jnp.float32),
            jax.ShapeDtypeStruct((n_rows, dim), jnp.float32),
            jax.ShapeDtypeStruct((n_rows,), jnp.int32),
        )
        return lowered.compile()
