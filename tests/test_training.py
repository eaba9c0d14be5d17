"""Training stack tests: optimizers descend, checkpoint/restart is
bit-exact, error-feedback compression converges, straggler flagging."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compat import make_mesh
from repro.configs import get_config
from repro.data.pipeline import Prefetcher, SyntheticTokens, make_batch_fn
from repro.models.registry import build_model
from repro.runtime import StragglerMonitor, TrainSupervisor
from repro.checkpoint import Checkpointer
from repro.train import make_optimizer, make_train_step, init_train_state
from repro.train.optimizer import cosine_schedule, wsd_schedule
from repro.train import grad_compression as gc


def _tiny_model():
    cfg = get_config("minicpm-2b").smoke().scaled(n_layers=2)
    return cfg, build_model(cfg)


def test_optimizers_descend():
    cfg, model = _tiny_model()
    src = SyntheticTokens(cfg.vocab_size, 16, 4, seed=3)
    batch_fn = make_batch_fn(src)
    for name in ["adamw", "adafactor"]:
        opt = make_optimizer(name, cosine_schedule(1e-2, 5, 200))
        state = init_train_state(model, opt, jax.random.key(0))
        step = jax.jit(make_train_step(model, opt))
        losses = []
        for s in range(20):
            state, metrics = step(state, batch_fn(s % 2))  # 2 repeating batches
            losses.append(float(metrics["loss"]))
        assert losses[-1] < losses[0] - 0.1, (name, losses[0], losses[-1])
        assert np.all(np.isfinite(losses))


def test_wsd_schedule_shape():
    lr = wsd_schedule(1.0, warmup=10, stable=50, decay=40)
    assert float(lr(0)) == 0.0
    assert abs(float(lr(10)) - 1.0) < 1e-6
    assert abs(float(lr(40)) - 1.0) < 1e-6
    assert float(lr(80)) < 1.0
    assert abs(float(lr(100)) - 0.1) < 1e-2


def test_checkpoint_roundtrip(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=2)
    tree = {"a": jnp.arange(5), "b": {"c": jnp.ones((2, 3)) * 1.5}}
    ck.save(1, tree, meta={"next_step": 1})
    ck.save(7, tree, meta={"next_step": 7})
    ck.save(9, tree, meta={"next_step": 9})
    assert ck.all_steps() == [7, 9]  # keep=2 gc'd step 1
    got, meta = ck.restore()
    assert meta["next_step"] == 9
    np.testing.assert_array_equal(np.asarray(got["a"]), np.arange(5))
    np.testing.assert_allclose(np.asarray(got["b"]["c"]), 1.5 * np.ones((2, 3)))


def test_restart_bit_exact(tmp_path):
    """Kill training at step 7, restart, resume: final params identical to
    an uninterrupted run (batches are pure functions of the step)."""
    cfg, model = _tiny_model()
    opt = make_optimizer("adamw", cosine_schedule(1e-2, 2, 100))
    src = SyntheticTokens(cfg.vocab_size, 16, 4, seed=5)
    batch_fn = make_batch_fn(src)
    step_fn = jax.jit(make_train_step(model, opt))
    N = 12

    # uninterrupted
    state = init_train_state(model, opt, jax.random.key(1))
    for s in range(N):
        state, _ = step_fn(state, batch_fn(s))
    ref = state["params"]

    # supervised with injected failure at step 7 (after ckpt at step 5)
    boom = {"armed": True}

    def failure_hook(step):
        if step == 7 and boom["armed"]:
            boom["armed"] = False
            raise RuntimeError("simulated node failure")

    sup = TrainSupervisor(str(tmp_path / "ck"), ckpt_every=5)
    st2 = sup.run(
        init_train_state(model, opt, jax.random.key(1)),
        step_fn,
        batch_fn,
        N,
        failure_hook=failure_hook,
    )
    assert sup.restarts == 1
    for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(st2["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_elastic_restore_new_sharding(tmp_path):
    """Checkpoints restore onto a different mesh (elastic resume)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    ck = Checkpointer(str(tmp_path))
    tree = {"w": jnp.arange(16.0).reshape(4, 4)}
    ck.save(3, tree, meta={"next_step": 3})
    mesh = make_mesh((1,), ("data",))
    sh = NamedSharding(mesh, P("data", None))
    got, _ = ck.restore(shardings=sh)
    assert got["w"].sharding == sh
    np.testing.assert_allclose(np.asarray(got["w"]), np.arange(16.0).reshape(4, 4))


def test_grad_compression_quantize_exact_roundtrip():
    x = jax.random.normal(jax.random.key(0), (1000,)) * 3.0
    q, s, r = gc.quantize(x)
    back = gc.dequantize(q, s, x.shape)
    np.testing.assert_allclose(np.asarray(back + r), np.asarray(x), rtol=1e-5, atol=1e-5)
    # quantization error bounded by scale/2 per element
    assert float(jnp.max(jnp.abs(r))) <= float(jnp.max(s)) * 0.51


def test_error_feedback_convergence():
    """EF-int8 SGD on a quadratic matches exact SGD to high accuracy."""
    dim = 64
    A = jax.random.normal(jax.random.key(1), (dim, dim)) / np.sqrt(dim)
    H = A @ A.T + 0.1 * jnp.eye(dim)
    b = jax.random.normal(jax.random.key(2), (dim,))

    def grad(x):
        return H @ x - b

    lr = 0.1
    x_exact = jnp.zeros(dim)
    x_comp = jnp.zeros(dim)
    err = jnp.zeros(dim)
    for _ in range(300):
        x_exact = x_exact - lr * grad(x_exact)
        g = grad(x_comp) + err
        q, s, err = gc.quantize(g)
        x_comp = x_comp - lr * gc.dequantize(q, s, g.shape)
    ref = jnp.linalg.solve(H, b)
    # EF-compressed SGD must track exact SGD tightly...
    assert float(jnp.linalg.norm(x_comp - x_exact)) < 1e-3
    # ...and make the same progress toward the optimum
    assert float(jnp.linalg.norm(x_comp - ref)) < float(jnp.linalg.norm(ref)) * 0.5


def test_straggler_monitor():
    m = StragglerMonitor(threshold=2.0, warmup=2)
    flags = [m.record(i, 1.0) for i in range(6)]
    assert not any(flags)
    assert m.record(6, 5.0) is True  # 5x the EWMA
    assert m.record(7, 1.0) is False
    assert m.flagged and m.flagged[0][0] == 6


def test_prefetcher_resumable():
    src = SyntheticTokens(100, 8, 2, seed=9)
    fn = make_batch_fn(src)
    pf = Prefetcher(fn, start_step=5, depth=2)
    s, b = pf.next()
    pf.close()
    assert s == 5
    np.testing.assert_array_equal(b["tokens"], fn(5)["tokens"])


def test_memmap_tokens(tmp_path):
    from repro.data.pipeline import MemmapTokens

    arr = np.arange(1000, dtype=np.int32)
    path = str(tmp_path / "corpus.bin")
    arr.tofile(path)
    ds = MemmapTokens(path, seq_len=16, global_batch=4)
    b0 = ds.batch_at(0)
    b0_again = ds.batch_at(0)
    np.testing.assert_array_equal(b0["tokens"], b0_again["tokens"])
    np.testing.assert_array_equal(b0["labels"][:, :-1], b0["tokens"][:, 1:])
