"""Runtime tests: train loop learns + checkpoints + resumes bitwise;
watchdog flags stragglers; serving engine equivalences."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import TrainConfig
from repro.data import SyntheticLM, make_batches
from repro.launch.mesh import make_host_mesh
from repro.models import model_zoo
from repro.runtime import fault_tolerance as ft
from repro.runtime import serve_loop, train_loop


@pytest.fixture(scope="module")
def small():
    cfg = model_zoo.reduced_config(model_zoo.get_config("deepseek-7b"))
    return cfg, model_zoo.build(cfg)


def test_train_loss_decreases(small, tmp_path):
    cfg, _ = small
    tc = TrainConfig(steps=8, learning_rate=2e-3, warmup_steps=1,
                     checkpoint_every=4)
    mesh = make_host_mesh()
    src = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=64, batch_size=4)
    state, hist = train_loop.train(cfg, tc, mesh, make_batches(src),
                                   ckpt_dir=str(tmp_path), log_every=1)
    assert len(hist) == 8
    assert hist[-1]["loss"] < hist[0]["loss"]
    assert int(state.step) == 8


def test_resume_is_bitwise_deterministic(small, tmp_path):
    """Fault-tolerance invariant: train 6 straight == train 3, checkpoint,
    restart, train 3 more — bit-identical params (data stream is a pure
    function of step, optimizer is deterministic)."""
    cfg, _ = small
    mesh = make_host_mesh()
    src = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=32, batch_size=2)

    tc6 = TrainConfig(steps=6, learning_rate=1e-3, warmup_steps=2,
                      checkpoint_every=100)
    s_straight, _ = train_loop.train(cfg, tc6, mesh, make_batches(src),
                                     log_every=100)

    tc3 = TrainConfig(steps=3, learning_rate=1e-3, warmup_steps=2,
                      checkpoint_every=3)
    d = str(tmp_path / "ck")
    train_loop.train(cfg, tc3, mesh, make_batches(src), ckpt_dir=d,
                     log_every=100)
    from repro.checkpoint import CheckpointManager
    mgr = CheckpointManager(d)
    like = train_loop.abstract_state(cfg, tc6)
    state, start = ft.resume_or_init(
        mgr, lambda: train_loop.init_state(cfg, tc6), like,
        shardings=train_loop.state_shardings(like, mesh))
    assert start == 3
    s_resumed, _ = train_loop.train(
        cfg, tc6, mesh, make_batches(src, start_step=start), state=state,
        start_step=start, log_every=100)
    for a, b in zip(jax.tree.leaves(s_straight.params),
                    jax.tree.leaves(s_resumed.params)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_num_microbatches():
    assert train_loop.num_microbatches(256, 16, 1) == 16
    assert train_loop.num_microbatches(256, 16, 16) == 1
    assert train_loop.num_microbatches(256, 32, 1) == 8
    assert train_loop.num_microbatches(1, 16, 1) == 1
    # non-dividing per_device rounds down to a divisor
    assert train_loop.num_microbatches(12, 1, 5) == 2


def test_watchdog_flags_injected_straggler():
    events = []
    wd = ft.StepWatchdog(factor=3.0, warmup=1,
                         on_straggler=events.append)
    for _ in range(5):
        wd.record(0.1)
    assert wd.record(1.0) is True           # 10x EMA
    assert len(events) == 1
    # EMA not poisoned by the straggler sample
    assert wd.ema < 0.2
    assert wd.record(0.1) is False


def test_graceful_shutdown_flag():
    import os
    import signal
    gs = ft.GracefulShutdown(signals=(signal.SIGUSR1,)).install()
    assert not gs.requested
    os.kill(os.getpid(), signal.SIGUSR1)
    assert gs.requested
    gs.uninstall()


def test_engine_packed_matches_raw(small):
    cfg, params = small
    prompts = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 12)),
        jnp.int32)
    out_p, _ = serve_loop.Engine(cfg, params, max_len=64,
                                 packed=True).generate(prompts, 6)
    out_r, _ = serve_loop.Engine(cfg, params, max_len=64,
                                 packed=False).generate(prompts, 6)
    np.testing.assert_array_equal(np.asarray(out_p), np.asarray(out_r))


def test_engine_slot_pool_serves_all(small):
    cfg, params = small
    eng = serve_loop.Engine(cfg, params, max_len=64, packed=True)
    rng = np.random.default_rng(1)
    reqs = [rng.integers(0, cfg.vocab_size, rng.integers(3, 10))
            .astype(np.int32) for _ in range(5)]
    outs, stats = eng.serve(reqs, batch_slots=2, max_new_tokens=4,
                            prefill_chunk=8, page_size=8)
    assert len(outs) == 5
    assert all(o.shape == (4,) for o in outs)
    assert stats.decode_tokens == 5 * 4
    assert stats.prefill_tokens == sum(len(r) for r in reqs)
