"""Checkpoint atomicity/reshard, fault-tolerant loop, elastic restart,
gradient compression, demand paging."""
import os
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import checkpoint as ckpt
from repro.distributed import compression as comp
from repro.runtime import elastic
from repro.runtime.fault_tolerance import (
    HeartbeatTracker, LoopConfig, PreemptionHandler, retry_step,
    run_training_loop,
)


def _tree(rng):
    return {
        "a": rng.standard_normal((8, 16)).astype(np.float32),
        "nested": {"b": rng.standard_normal((4,)).astype(np.float32),
                   "c": np.int32(7)},
    }


def test_checkpoint_roundtrip_and_keep(tmp_path, rng):
    t1 = _tree(rng)
    for step in (10, 20, 30, 40):
        ckpt.save(tmp_path, step, t1, keep=2)
    assert ckpt.latest_step(tmp_path) == 40
    kept = sorted(p.name for p in pathlib.Path(tmp_path).iterdir())
    assert kept == ["step_00000030", "step_00000040"]
    restored, step = ckpt.restore(tmp_path, template=t1)
    assert step == 40
    np.testing.assert_array_equal(restored["a"], t1["a"])
    np.testing.assert_array_equal(restored["nested"]["b"], t1["nested"]["b"])


def test_checkpoint_atomic_no_partial_reads(tmp_path, rng):
    t1 = _tree(rng)
    ckpt.save(tmp_path, 1, t1)
    # A stale tmp dir from a "crashed" writer must be ignored and swept.
    junk = pathlib.Path(tmp_path) / "step_00000002.tmp-dead"
    junk.mkdir()
    (junk / "garbage.npy").write_bytes(b"xx")
    assert ckpt.latest_step(tmp_path) == 1
    ckpt.save(tmp_path, 3, t1)
    assert not junk.exists()


def test_async_checkpointer(tmp_path, rng):
    t1 = _tree(rng)
    ac = ckpt.AsyncCheckpointer(tmp_path, keep=2)
    for s in (5, 10):
        ac.submit(s, t1)
    ac.close()
    assert ckpt.latest_step(tmp_path) == 10


def test_elastic_restore_onto_smaller_mesh(tmp_path):
    """Save on one layout, restore resharded onto a different device count."""
    from repro.configs import registry
    from repro import models
    from repro.train.optimizer import init_state

    cfg = registry.get_smoke("qwen3-14b")
    params = models.init(jax.random.PRNGKey(0), cfg)
    opt = init_state(params)
    ckpt.save(tmp_path, 100, {"params": params, "opt_state": opt})

    plan = elastic.plan_remesh(available_devices=1, model_axis=1)
    assert plan.shape == (1, 1)
    (state, step, mesh) = elastic.elastic_restore(
        tmp_path, cfg, plan, {"params": params, "opt_state": opt},
    )
    assert step == 100
    chk = jax.tree.map(
        lambda a, b: np.allclose(np.asarray(a, np.float32), np.asarray(b, np.float32)),
        state["params"], params,
    )
    assert all(jax.tree.leaves(chk))


def test_heartbeat_straggler_detection():
    tr = HeartbeatTracker(straggler_factor=1.5)
    for host in range(8):
        for _ in range(5):
            tr.record(host, 1.0 if host != 3 else 2.5)
    assert tr.stragglers() == [3]


def test_retry_step_recovers():
    from jax.errors import JaxRuntimeError

    calls = {"n": 0}

    def flaky(x):
        calls["n"] += 1
        if calls["n"] < 3:
            raise JaxRuntimeError("UNAVAILABLE: transient backend hiccup")
        return x + 1

    assert retry_step(flaky, 41, retries=3, base_delay_s=0) == 42
    with pytest.raises(JaxRuntimeError):
        retry_step(lambda: (_ for _ in ()).throw(
            JaxRuntimeError("UNAVAILABLE: always")), retries=1, base_delay_s=0)


def test_is_transient_classification():
    import errno

    from repro.runtime.fault_tolerance import is_transient

    # Retryable environment hiccups.
    assert is_transient(MemoryError())
    assert is_transient(TimeoutError())
    assert is_transient(ConnectionResetError(errno.ECONNRESET, "reset"))
    assert is_transient(InterruptedError(errno.EINTR, "interrupted"))
    assert is_transient(OSError(errno.EIO, "flaky disk"))
    # XLA runtime faults with a retryable status code.
    from jax.errors import JaxRuntimeError

    assert is_transient(JaxRuntimeError("RESOURCE_EXHAUSTED: out of memory"))
    # Deterministic filesystem failures surface immediately: retrying a
    # missing file / bad permission / full disk just replays the failure.
    assert not is_transient(FileNotFoundError(errno.ENOENT, "gone"))
    assert not is_transient(PermissionError(errno.EACCES, "denied"))
    assert not is_transient(IsADirectoryError(errno.EISDIR, "a dir"))
    assert not is_transient(OSError(errno.ENOSPC, "disk full"))
    # Program bugs are never transient.
    assert not is_transient(ValueError("bad config"))
    assert not is_transient(RuntimeError("refusing to overwrite history"))
    assert not is_transient(RuntimeError("RESOURCE_EXHAUSTED: not from XLA"))
    assert not is_transient(NotImplementedError())


def test_training_loop_checkpoints_and_preempts(tmp_path):
    from repro.configs import registry
    from repro import models
    from repro.train.optimizer import OptimizerConfig, init_state
    from repro.train.train_step import make_train_step

    cfg = registry.get_smoke("stablelm-12b")
    params = models.init(jax.random.PRNGKey(0), cfg)
    opt = init_state(params)
    step_fn = jax.jit(make_train_step(cfg, OptimizerConfig(lr=1e-3, warmup_steps=1)))
    rng = np.random.default_rng(0)

    def batch_fn(step):
        return {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (2, 16)).astype(np.int32))}

    pre = PreemptionHandler(install=False)
    losses = []

    def on_metrics(step, m):
        losses.append(float(m["loss"]))
        if step == 5:
            pre.requested = True  # simulated SIGTERM

    state, stopped = run_training_loop(
        step_fn, (params, opt), batch_fn, tmp_path,
        LoopConfig(total_steps=100, checkpoint_every=3),
        preemption=pre, on_metrics=on_metrics,
    )
    assert stopped == 6                      # checkpoint-and-exit at the boundary
    assert ckpt.latest_step(tmp_path) == 6   # preemption checkpoint committed
    assert all(np.isfinite(losses))


def test_topk_error_feedback_conserves_gradient():
    g = {"w": jnp.asarray(np.random.default_rng(0).standard_normal((32, 32)), jnp.float32)}
    err = comp.init_error_state(g)
    kept, err = comp.topk_compress(g, err, ratio=0.1)
    # kept + error == original (nothing lost, just deferred)
    np.testing.assert_allclose(
        np.asarray(kept["w"]) + np.asarray(err["w"]), np.asarray(g["w"]), atol=1e-6)
    nz = float((np.asarray(kept["w"]) != 0).mean())
    assert nz <= 0.15


def test_int8_roundtrip_error_bounded():
    g = {"w": jnp.asarray(np.random.default_rng(0).standard_normal((16, 64)), jnp.float32)}
    out = comp.int8_roundtrip(g)
    err = np.abs(np.asarray(out["w"]) - np.asarray(g["w"])).max()
    scale = np.abs(np.asarray(g["w"])).max(axis=-1).max()
    assert err <= scale / 127.0 + 1e-6
    assert comp.compressed_bytes(g, comp.CompressionConfig("int8")) == g["w"].size


def test_os_model_shared_mapping_adjustment():
    """Paper §5 worked example: [V5..V9] with partitions (3,0,1,2,3), P=4 -> V7."""
    from repro.core.pagetable import adjust_virtual_region, alloc_page_vma, make_partitions
    assert adjust_virtual_region(5, [3, 0, 1, 2, 3], 4) == 7
    parts = make_partitions(4, frames_per_partition=8)
    p, frame = alloc_page_vma(vaddr_vpn=6, asid=1, partitions=parts)
    assert p == 6 % 4
    assert parts[p].page_table.lookup(1, 6) == frame
    assert parts[p].page_table.invalidate(1, 6)
    assert parts[p].page_table.lookup(1, 6) is None
