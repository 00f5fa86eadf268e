"""Training substrate: optimizer behaviour, step-atomic checkpoint/restart,
resume-after-crash, gradient accumulation equivalence."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import ARCHS
from repro.core.object_store import MemoryObjectStore
from repro.models import model as M
from repro.train.checkpoint import (
    committed_steps,
    prune_checkpoints,
    restore_latest,
    save_checkpoint,
)
from repro.train.loop import TrainConfig, train
from repro.train.optimizer import (
    AdamWConfig,
    adamw_update,
    clip_by_global_norm,
    global_norm,
    init_opt_state,
)


def test_adamw_converges_quadratic():
    params = {"w": jnp.array([5.0, -3.0])}
    opt = init_opt_state(params)
    cfg = AdamWConfig(lr=0.2, weight_decay=0.0, warmup_steps=1)
    for _ in range(200):
        grads = jax.grad(lambda p: jnp.sum(p["w"] ** 2))(params)
        params, opt = adamw_update(cfg, params, grads, opt)
    assert float(jnp.abs(params["w"]).max()) < 1e-2


def test_grad_clip():
    g = {"a": jnp.full((10,), 100.0)}
    clipped, norm = clip_by_global_norm(g, 1.0)
    assert float(norm) > 1.0
    np.testing.assert_allclose(float(global_norm(clipped)), 1.0, rtol=1e-5)
    # below the threshold: untouched
    g2 = {"a": jnp.full((4,), 0.01)}
    same, _ = clip_by_global_norm(g2, 1.0)
    np.testing.assert_allclose(np.asarray(same["a"]), np.asarray(g2["a"]))


def test_checkpoint_roundtrip_and_atomicity():
    store = MemoryObjectStore()
    cfg = ARCHS["yi-9b"].reduced(num_layers=2, d_model=32, num_heads=2,
                                 num_kv_heads=2, head_dim=8, d_ff=64, vocab_size=64)
    params = M.init_params(cfg, jax.random.key(0))
    opt = init_opt_state(params)
    save_checkpoint(store, "run", 10, params, opt, extra={"note": "x"})
    save_checkpoint(store, "run", 20, params, opt)
    assert committed_steps(store, "run") == [10, 20]
    step, p2, o2, _ = restore_latest(store, "run", params, opt)
    assert step == 20
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        params, p2,
    )
    # a partial checkpoint (no manifest) is invisible
    store.put("ckpt/run/0000000030/params/embed", b"garbage")
    assert committed_steps(store, "run") == [10, 20]
    prune_checkpoints(store, "run", keep=1)
    assert committed_steps(store, "run") == [20]


def test_train_resume_is_seamless():
    store = MemoryObjectStore()
    cfg = ARCHS["yi-9b"].reduced(num_layers=2, d_model=32, num_heads=2,
                                 num_kv_heads=2, head_dim=8, d_ff=64, vocab_size=64)
    tc = TrainConfig(steps=6, batch=2, seq_len=16, checkpoint_every=3,
                     log_every=100, run_name="resume-test")
    # full run
    _p_full, _o, losses_full = train(cfg, store, tc)
    # interrupted run: 3 steps, then a fresh process resumes to 6
    store2 = MemoryObjectStore()
    tc3 = TrainConfig(steps=3, batch=2, seq_len=16, checkpoint_every=3,
                      log_every=100, run_name="resume-test")
    train(cfg, store2, tc3)
    _p_res, _o2, losses_res = train(cfg, store2, tc)  # resumes at step 3
    assert len(losses_res) == 3  # only steps 3..6 executed
    np.testing.assert_allclose(losses_full[3:], losses_res, rtol=2e-4, atol=2e-4)


def test_microbatch_grads_match_full_batch():
    """Gradient accumulation over N microbatches == one full-batch step."""
    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import build_train_cell
    from repro.models.config import ShapeConfig

    mesh = make_host_mesh(1, 1)
    cfg = ARCHS["yi-9b"].reduced(num_layers=2, d_model=32, num_heads=2,
                                 num_kv_heads=2, head_dim=8, d_ff=64, vocab_size=64)
    shape = ShapeConfig("t", seq_len=16, global_batch=4, kind="train")
    batch = {
        "tokens": jax.random.randint(jax.random.key(1), (4, 16), 0, 64),
        "labels": jax.random.randint(jax.random.key(2), (4, 16), 0, 64),
    }
    params = M.init_params(cfg, jax.random.key(0))
    opt = init_opt_state(params)
    outs = []
    with mesh:
        for mb in (1, 2):
            step, shardings, _structs, _don = build_train_cell(
                cfg, shape, mesh, microbatches=mb)
            p2, _o2, metrics = jax.jit(step)(params, opt, batch)
            outs.append((p2, float(metrics["loss"])))
    np.testing.assert_allclose(outs[0][1], outs[1][1], rtol=1e-3)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), rtol=2e-2, atol=2e-3),
        outs[0][0], outs[1][0],
    )
