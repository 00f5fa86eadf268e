"""Multi-device tests: run in SUBPROCESSES with forced host device counts
(conftest deliberately leaves the main process at 1 device)."""

import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_subprocess(script: str, devices: int = 8, timeout: int = 480) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(script)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return out.stdout


def test_distributed_search_matches_bruteforce():
    run_subprocess("""
    import numpy as np, jax
    from repro.distributed.search import distributed_search_host
    from repro.launch.mesh import make_host_mesh
    rng = np.random.default_rng(0)
    base = rng.standard_normal((999, 24)).astype(np.float32)   # uneven => pad path
    q = rng.standard_normal((4, 24)).astype(np.float32)
    mesh = make_host_mesh(2, 4)
    vals, idx = distributed_search_host(q, base, 10, "l2", mesh)
    d2 = np.sum(q**2,1,keepdims=True) - 2*q@base.T + np.sum(base**2,1)
    gt = np.argsort(d2,axis=1)[:, :10]
    assert (np.sort(idx,1) == np.sort(gt,1)).all()
    print("OK")
    """)


def test_flash_decode_matches_dense():
    """The shard_map flash-decode over a sequence-sharded KV cache must be
    numerically equivalent to dense decode attention."""
    run_subprocess("""
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.models.model import dense_gqa_decode_attn
    from repro.launch.mesh import make_host_mesh
    from repro.distributed.decode_attn import make_gqa_flash_decode

    mesh = make_host_mesh(2, 4)
    B, S, H, KVH, hd = 4, 32, 8, 2, 16
    rng = np.random.default_rng(0)
    q = rng.standard_normal((B, 1, H, hd)).astype(np.float32)
    k_new = rng.standard_normal((B, 1, KVH, hd)).astype(np.float32)
    v_new = rng.standard_normal((B, 1, KVH, hd)).astype(np.float32)
    kc = rng.standard_normal((B, S, KVH, hd)).astype(np.float32)
    vc = rng.standard_normal((B, S, KVH, hd)).astype(np.float32)
    pos = jnp.asarray(17, jnp.int32)

    ref_out, ref_k, ref_v = dense_gqa_decode_attn(
        jnp.asarray(q), jnp.asarray(k_new), jnp.asarray(v_new),
        jnp.asarray(kc), jnp.asarray(vc), pos)

    impl = make_gqa_flash_decode(mesh, "model", P("data"))
    with mesh:
        put = lambda a, spec: jax.device_put(a, NamedSharding(mesh, spec))
        out, k2, v2 = jax.jit(impl)(
            put(q, P("data")), put(k_new, P("data")), put(v_new, P("data")),
            put(kc, P("data", "model")), put(vc, P("data", "model")), pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(k2), np.asarray(ref_k), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(v2), np.asarray(ref_v), rtol=1e-5, atol=1e-5)
    print("OK")
    """)


def test_mla_flash_decode_matches_dense():
    run_subprocess("""
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.models.model import dense_mla_decode_attn
    from repro.launch.mesh import make_host_mesh
    from repro.distributed.decode_attn import make_mla_flash_decode

    mesh = make_host_mesh(2, 4)
    B, S, H, r, rope = 4, 32, 6, 16, 8
    rng = np.random.default_rng(0)
    q_c = rng.standard_normal((B, 1, H, r)).astype(np.float32)
    q_rope = rng.standard_normal((B, 1, H, rope)).astype(np.float32)
    payload = rng.standard_normal((B, 1, r + rope)).astype(np.float32)
    cc = rng.standard_normal((B, S, r + rope)).astype(np.float32)
    pos = jnp.asarray(9, jnp.int32)

    ref_ctx, ref_c = dense_mla_decode_attn(
        jnp.asarray(q_c), jnp.asarray(q_rope), jnp.asarray(payload),
        jnp.asarray(cc), pos, r, 24)

    impl = make_mla_flash_decode(mesh, "model", P("data"))
    with mesh:
        put = lambda a, spec: jax.device_put(a, NamedSharding(mesh, spec))
        ctx, c2 = jax.jit(lambda a,b,c,d,e: impl(a,b,c,d,e,r,24))(
            put(q_c, P("data")), put(q_rope, P("data")), put(payload, P("data")),
            put(cc, P("data", "model")), pos)
    np.testing.assert_allclose(np.asarray(ctx), np.asarray(ref_ctx), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(c2), np.asarray(ref_c), rtol=1e-5, atol=1e-5)
    print("OK")
    """)


def test_small_mesh_train_step_executes():
    """REAL multi-device execution of a full sharded train step (reduced
    arch, 2x2 mesh) — proves the partition specs are executable, not just
    compilable."""
    run_subprocess("""
    import numpy as np, jax, jax.numpy as jnp
    import dataclasses
    from repro.configs import ARCHS
    from repro.models import model as M
    from repro.launch.steps import build_train_cell
    from repro.models.config import ShapeConfig
    from repro.train.optimizer import init_opt_state
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(2, 2)
    cfg = ARCHS["yi-9b"].reduced(num_heads=4, num_kv_heads=2, d_model=64,
                                 head_dim=16, d_ff=128, vocab_size=256)
    shape = ShapeConfig("tiny_train", seq_len=32, global_batch=4, kind="train")
    step, shardings, structs, donate = build_train_cell(cfg, shape, mesh)

    params = M.init_params(cfg, jax.random.key(0))
    opt = init_opt_state(params)
    batch = {
        "tokens": jax.random.randint(jax.random.key(1), (4, 32), 0, 256),
        "labels": jax.random.randint(jax.random.key(2), (4, 32), 0, 256),
    }
    with mesh:
        params = jax.device_put(params, shardings[0])
        opt = jax.device_put(opt, shardings[1])
        batch = jax.device_put(batch, shardings[2])
        fn = jax.jit(step, in_shardings=shardings, donate_argnums=donate)
        p2, o2, metrics = fn(params, opt, batch)
        loss1 = float(metrics["loss"])
        p3, o3, metrics2 = fn(p2, o2, batch)
        loss2 = float(metrics2["loss"])
    assert np.isfinite(loss1) and np.isfinite(loss2)
    assert loss2 < loss1, (loss1, loss2)
    print("OK", loss1, loss2)
    """, devices=4)


def test_small_mesh_moe_shard_map_matches_dense():
    """Expert-parallel shard_map MoE == dense scatter MoE numerically."""
    run_subprocess("""
    import numpy as np, jax, jax.numpy as jnp
    from repro.configs import ARCHS
    from repro.models.moe import init_moe_params, moe_block
    from repro.distributed import act_sharding
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(2, 2)
    cfg = ARCHS["qwen3-moe-30b-a3b"].reduced()
    p = init_moe_params(cfg, jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (4, 16, cfg.d_model), jnp.float32)
    dense = moe_block(cfg, p, x)  # no policy -> dense path
    with mesh:
        with act_sharding.policy(mesh, ("data",), moe_impl="shard_map"):
            sharded = jax.jit(lambda x: moe_block(cfg, p, x))(x)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(sharded),
                               rtol=2e-2, atol=2e-2)
    print("OK")
    """, devices=4)


def test_dryrun_search_compiles_at_scale():
    """Distributed vector search lowers+compiles on the 16x16 mesh."""
    run_subprocess("""
    import jax
    from repro.launch.mesh import make_production_mesh
    from repro.distributed.search import dryrun_search
    mesh = make_production_mesh()
    compiled = dryrun_search(mesh, n_rows=256*4096, dim=128, nq=64, k=50)
    cost = compiled.cost_analysis()
    assert cost.get("flops", 0) > 0
    print("OK", cost.get("flops"))
    """, devices=256, timeout=560)
