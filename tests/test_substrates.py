"""Substrate tests: optimizer, gradient compression, checkpointing,
fault-tolerant driver, data pipeline, streaming service."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.optim.adamw import AdamWConfig, adamw_update, init_adamw, lr_schedule
from repro.optim.compression import compress, decompress, init_ef


def test_adamw_reduces_quadratic_loss():
    cfg = AdamWConfig(lr_peak=0.1, warmup_steps=5, total_steps=100,
                      weight_decay=0.0, clip_norm=1.0)
    target = jnp.asarray([1.0, -2.0, 3.0])
    params = {"w": jnp.zeros(3)}
    state = init_adamw(cfg, params)

    def loss_fn(p):
        return jnp.sum(jnp.square(p["w"] - target))

    loss0 = float(loss_fn(params))
    for _ in range(100):
        grads = jax.grad(loss_fn)(params)
        params, state, _m = adamw_update(cfg, params, grads, state)
    assert float(loss_fn(params)) < 0.05 * loss0


def test_adamw_bf16_moments_close_to_f32():
    target = jnp.asarray(np.random.RandomState(0).randn(32).astype(np.float32))

    def run(moment_dtype):
        cfg = AdamWConfig(lr_peak=0.05, warmup_steps=2, total_steps=60,
                          weight_decay=0.0, moment_dtype=moment_dtype)
        params = {"w": jnp.zeros(32)}
        state = init_adamw(cfg, params)
        for _ in range(60):
            grads = jax.grad(lambda p: jnp.sum((p["w"] - target) ** 2))(params)
            params, state, _ = adamw_update(cfg, params, grads, state)
        return params["w"]

    w32 = run("float32")
    w16 = run("bfloat16")
    # bf16 moments track f32 within a coarse tolerance (documented policy)
    assert float(jnp.max(jnp.abs(w32 - w16))) < 0.15


def test_lr_schedule_shape():
    cfg = AdamWConfig(lr_peak=1e-3, lr_min=1e-4, warmup_steps=10, total_steps=100)
    lrs = [float(lr_schedule(cfg, jnp.asarray(s))) for s in range(101)]
    assert lrs[0] == 0.0
    assert abs(lrs[10] - 1e-3) < 1e-9
    assert lrs[100] == pytest.approx(1e-4, rel=1e-3)
    assert all(a >= b - 1e-12 for a, b in zip(lrs[10:], lrs[11:]))  # decaying


def test_error_feedback_compression_contracts():
    """EF invariant: sum of dequantized transmissions + final residual equals
    the sum of raw gradients (no gradient information is lost over time)."""
    rng = np.random.RandomState(0)
    grads_seq = [{"w": jnp.asarray(rng.randn(64).astype(np.float32))} for _ in range(20)]
    ef = init_ef(grads_seq[0])
    sent = jnp.zeros(64)
    for g in grads_seq:
        q, s, ef = compress(g, ef)
        sent = sent + decompress(q, s)["w"]
    total = sum(g["w"] for g in grads_seq)
    np.testing.assert_allclose(
        np.asarray(sent + ef.residual["w"]), np.asarray(total), rtol=1e-5, atol=1e-5
    )
    # compression is tight: int8 with per-tensor scale -> bounded error
    assert float(jnp.max(jnp.abs(ef.residual["w"]))) < float(jnp.max(jnp.abs(total))) / 10


def test_checkpoint_roundtrip_and_atomicity():
    from repro.checkpoint import ckpt

    tree = {
        "params": {"w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4)},
        "step": jnp.asarray(7),
        "nested": [jnp.ones((2, 2), jnp.bfloat16), jnp.zeros((1,), jnp.int32)],
    }
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(d, 10, tree, extra={"cursor": 123})
        restored, extra = ckpt.restore(d, like=tree)
        assert extra["cursor"] == 123
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            assert a.dtype == b.dtype
        # a later, torn write must not be visible: fake a partial dir
        os.makedirs(os.path.join(d, "step_000000020.tmp.0"), exist_ok=True)
        restored2, _ = ckpt.restore(d, like=tree)
        np.testing.assert_array_equal(
            np.asarray(restored2["params"]["w"]), np.asarray(tree["params"]["w"])
        )


def test_checkpoint_async_then_restore():
    from repro.checkpoint import ckpt

    with tempfile.TemporaryDirectory() as d:
        tree = {"w": jnp.ones((4,))}
        ckpt.async_save(d, 1, tree, extra={"step": 1})
        ckpt.wait_pending(d)
        restored, extra = ckpt.restore(d, like=tree)
        assert extra["step"] == 1


def test_checkpoint_async_save_failure_reraised_at_wait_pending():
    """A real IO failure on the saver thread is not lost: wait_pending
    re-raises it (only the fault harness's SimulatedCrash is swallowed),
    and the slot is cleared so the next save starts clean."""
    from repro.checkpoint import ckpt

    with tempfile.TemporaryDirectory() as d:
        blocker = os.path.join(d, "blocker")
        with open(blocker, "w") as f:
            f.write("not a directory")
        target = os.path.join(blocker, "ckpt")
        ckpt.async_save(target, 1, {"w": jnp.ones((4,))})
        with pytest.raises(OSError):
            ckpt.wait_pending(target)
        ckpt.wait_pending(target)  # already surfaced: nothing pending


def test_run_with_restarts_recovers_from_crash():
    from repro.distributed.fault import run_with_restarts

    crashed = {"done": False}

    def step_fn(state, step):
        if step == 7 and not crashed["done"]:
            crashed["done"] = True
            raise RuntimeError("injected node failure")
        return {"x": state["x"] + 1.0}

    with tempfile.TemporaryDirectory() as d:
        final, info = run_with_restarts(
            step_fn, {"x": jnp.zeros(())}, n_steps=12, ckpt_dir=d, ckpt_every=5,
        )
        assert info["restarts"] == 1
        assert info["final_step"] == 12
        assert float(final["x"]) == 12.0  # exactly-once semantics via resume


def test_straggler_monitor():
    from repro.distributed.fault import StragglerMonitor

    mon = StragglerMonitor(deadline_factor=3.0, warmup=3)
    for i in range(10):
        mon.observe(i, 0.1)
    assert mon.observe(10, 1.0)       # 10x median -> straggler
    assert not mon.observe(11, 0.12)
    assert mon.stragglers == [10]


def test_token_pipeline_determinism_and_cursor():
    from repro.data.tokens import TokenPipeline

    p1 = TokenPipeline(vocab_size=100, seq_len=16, batch_per_host=4, seed=1)
    a = next(p1)
    b = next(p1)
    p1.close()
    # resume from cursor=1 reproduces batch #1 exactly
    p2 = TokenPipeline(vocab_size=100, seq_len=16, batch_per_host=4, seed=1,
                       start_step=1)
    b2 = next(p2)
    p2.close()
    np.testing.assert_array_equal(b["tokens"], b2["tokens"])
    assert not np.array_equal(a["tokens"], b["tokens"])


def test_service_end_to_end_with_expiry_and_ckpt():
    from repro.streaming.generators import so_like
    from repro.streaming.service import PersistentQueryService

    stream = so_like(n_vertices=24, n_edges=150, seed=3, rate=10.0)
    svc = PersistentQueryService(window=5.0, slide=1.0)
    svc.register("q1", "a2q . c2a*", engine="dense", n_slots=48)
    svc.register("q1_ref", "a2q . c2a*", engine="reference")
    svc.ingest(stream)
    assert svc.results("q1") == svc.results("q1_ref")
    assert svc.stats["q1"].tuples == len(stream)

    with tempfile.TemporaryDirectory() as d:
        svc.snapshot(d, step=1)
        # new service instance re-attaches to the persisted state
        svc2 = PersistentQueryService(window=5.0, slide=1.0)
        svc2.register("q1", "a2q . c2a*", engine="dense", n_slots=48)
        svc2.restore(d)
        assert svc2.results("q1") == svc.results("q1")
