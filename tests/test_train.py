"""Training subsystem: loop integration, accumulation equivalence,
checkpoint/restore, fault tolerance, optimizer, QSGD."""
import dataclasses
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import OptimizerConfig, load_config
from repro.quant import qsgd
from repro.train import optimizer as opt_lib
from repro.train import train_loop
from repro.train.checkpoint import CheckpointManager
from repro.train.fault_tolerance import (PreemptionGuard, StepWatchdog,
                                         StragglerEvent, retry)


def _tiny_cfg(**train_kw):
    cfg = load_config("tiny")
    return dataclasses.replace(
        cfg, train=dataclasses.replace(cfg.train, **train_kw))


def test_loss_decreases_tiny_lm():
    cfg = _tiny_cfg(adapt_interval=10, log_every=2)
    state, hist = train_loop.train(cfg, steps=24, log=lambda s: None)
    losses = [h["loss"] for h in hist]
    assert losses[-1] < losses[0]


def test_float32_mode_trains_too():
    cfg = load_config("tiny", overrides=["quant.mode=off"])
    cfg = dataclasses.replace(
        cfg, train=dataclasses.replace(cfg.train, log_every=2))
    state, hist = train_loop.train(cfg, steps=24, log=lambda s: None)
    first3 = sum(h["loss"] for h in hist[:3]) / 3
    last3 = sum(h["loss"] for h in hist[-3:]) / 3
    assert last3 < first3 + 1e-3          # trending down (12 samples, noisy)
    assert state["adapt"]["tensors"] == {}


def test_accumulation_matches_full_batch():
    """accum_steps=4 must produce (nearly) the same update as accum=1 with
    the same global batch: grads are means over the same tokens."""
    results = {}
    for accum in (1, 4):
        cfg = _tiny_cfg(accum_steps=accum, seq_len=32, global_batch=8)
        cfg = dataclasses.replace(
            cfg, quant=dataclasses.replace(cfg.quant,
                                           stochastic_rounding=False))
        state = train_loop.init_state(cfg)
        step = jax.jit(train_loop.make_train_step(cfg))
        batch = train_loop.make_batch(cfg, 0)
        new_state, metrics = step(state, batch)
        results[accum] = (new_state, metrics)
    l1, l4 = results[1][1]["loss"], results[4][1]["loss"]
    assert abs(float(l1) - float(l4)) < 5e-3
    p1 = jax.tree_util.tree_leaves(results[1][0]["params"])
    p4 = jax.tree_util.tree_leaves(results[4][0]["params"])
    err = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                    - b.astype(jnp.float32))))
              for a, b in zip(p1, p4))
    assert err < 5e-3, f"accum mismatch {err}"


def test_checkpoint_roundtrip_and_resume():
    cfg = _tiny_cfg()
    state, _ = train_loop.train(cfg, steps=3, log=lambda s: None)
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, keep=2, async_save=False)
        mgr.save(state, step=3)
        restored = mgr.restore(train_loop.init_state(cfg))
        for a, b in zip(jax.tree_util.tree_leaves(state),
                        jax.tree_util.tree_leaves(restored)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32))
        st2, _ = train_loop.train(cfg, steps=2, state=restored,
                                  log=lambda s: None)
        assert int(st2["step"]) == 5


def test_checkpoint_gc_and_torn_write():
    cfg = _tiny_cfg()
    state = train_loop.init_state(cfg)
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, keep=2, async_save=False)
        for s in (1, 2, 3):
            mgr.save(state, step=s)
        assert mgr.all_steps() == [2, 3]          # GC kept last 2
        # torn write: directory without DONE must be ignored
        os.makedirs(os.path.join(d, "step_00000009"))
        assert mgr.latest_step() == 3
        # CRC failure detection
        with open(os.path.join(d, "step_00000003", "arrays.npz"), "ab") as f:
            f.write(b"corrupt")
        with pytest.raises(IOError):
            mgr.restore(train_loop.init_state(cfg), step=3)


def test_watchdog_flags_stragglers():
    events = []
    wd = StepWatchdog(factor=3.0, min_samples=3,
                      on_straggler=lambda s, dt, med: events.append(s))
    for i in range(6):
        wd.observe(i, 0.1)
    assert not events
    assert wd.observe(6, 1.0)
    assert events == [6]
    wd2 = StepWatchdog(factor=2.0, min_samples=2, max_consecutive=2)
    wd2.observe(0, 0.1)
    wd2.observe(1, 0.1)
    wd2.observe(2, 1.0)
    with pytest.raises(StragglerEvent):
        wd2.observe(3, 1.0)


def test_dt_is_wall_time_per_step_between_loss_reads():
    """``dt`` spans two host syncs (the loss reads every ``log_every``
    steps), so the intervals add up to the loop's wall time."""
    import time
    cfg = _tiny_cfg(log_every=2)
    state = train_loop.init_state(cfg)
    wd = StepWatchdog()
    t0 = time.perf_counter()
    _, hist = train_loop.train(cfg, steps=6, state=state, watchdog=wd,
                               log=lambda s: None)
    wall = time.perf_counter() - t0
    assert [h["step"] for h in hist] == [2, 4, 6]
    assert wd.times == [h["dt"] for h in hist]
    covered = sum(2 * h["dt"] for h in hist)
    assert 0.5 * wall < covered <= wall


def test_trace_steps_write_host_spans(tmp_path):
    """``train(trace=(dir, 1, 3))`` profiles steps 1 and 2: the per-step
    annotation and the host spans land in the written ``.xplane.pb``."""
    import glob
    from jax.profiler import ProfileData
    cfg = _tiny_cfg(adapt_interval=2, log_every=1, checkpoint_every=2)
    mgr = CheckpointManager(str(tmp_path / "ck"), keep=1, async_save=False)
    train_loop.train(cfg, steps=4, checkpoint_mgr=mgr, log=lambda s: None,
                     trace=(str(tmp_path / "prof"), 1, 3))
    path, = glob.glob(str(tmp_path / "prof" / "**" / "*.xplane.pb"),
                      recursive=True)
    spans = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name == "train" or e.name.startswith("train."):
                    spans.setdefault(e.name, []).append(
                        dict(e.stats).get("step_num"))
    assert set(spans) == {"train", "train.batch", "train.step",
                          "train.switch", "train.log_read",
                          "train.checkpoint"}
    assert sorted(spans["train"]) == [1, 2]
    assert len(spans["train.step"]) == 2
    assert len(spans["train.switch"]) == len(spans["train.checkpoint"]) == 1


def test_retry_and_preemption_guard():
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise IOError("transient")
        return 42

    assert retry(flaky, attempts=4, base_delay=0.0) == 42
    with PreemptionGuard() as g:
        assert not g.requested
        import signal
        os.kill(os.getpid(), signal.SIGTERM)
        assert g.requested


def test_rop_scheduler_reduces_lr():
    ocfg = OptimizerConfig(lr=0.1, rop_patience=3, rop_factor=0.5,
                           rop_threshold=1e-3)
    st = opt_lib.init_opt_state({"w": jnp.zeros(2)}, ocfg)
    # call 1 establishes best=1.0; calls 2-4 are the 3 plateau steps
    for _ in range(4):
        st = opt_lib.rop_update(st, jnp.float32(1.0), ocfg)
    assert float(st["lr"]) == pytest.approx(0.05)
    # improvement resets patience
    st = opt_lib.rop_update(st, jnp.float32(0.5), ocfg)
    assert int(st["rop_bad"]) == 0


def test_grad_normalization_targets_quantized_only():
    grads = {"a": jnp.ones((4, 4)) * 10.0, "b": jnp.ones((4,)) * 10.0}
    out = opt_lib.normalize_grads(grads, {"a"})
    assert float(jnp.linalg.norm(out["a"])) == pytest.approx(1.0, rel=1e-5)
    assert float(jnp.max(out["b"])) == 10.0


@pytest.mark.parametrize("bits", [4, 8])
def test_qsgd_unbiased_and_bounded(bits):
    key = jax.random.PRNGKey(0)
    g = jax.random.normal(key, (512,)) * 2.0
    reps = 300
    decs = [qsgd.decode(*qsgd.encode(g, jax.random.fold_in(key, i), bits))
            for i in range(reps)]
    mean = jnp.mean(jnp.stack(decs), axis=0)
    step = float(jnp.max(jnp.abs(g))) / (2 ** (bits - 1) - 1)
    assert float(jnp.max(jnp.abs(mean - g))) < 4 * step / np.sqrt(reps) * 3
    # single-shot error bounded by one quantization step
    one = qsgd.decode(*qsgd.encode(g, key, bits))
    assert float(jnp.max(jnp.abs(one - g))) <= step + 1e-6


def test_adapt_interval_cadence():
    """Controller switches happen every adapt_interval steps, never inside
    the hot step."""
    cfg = _tiny_cfg(adapt_interval=5)
    telemetry = []
    state, _ = train_loop.train(cfg, steps=11, telemetry=telemetry,
                                log=lambda s: None)
    assert len(telemetry) == 2   # steps 5 and 10
