"""Chaos gates: deterministic fault injection against the serving stack.

The headline guarantee (ISSUE: request-level fault isolation): under
EVERY injected fault mix, requests that complete return tokens
bit-identical to a fault-free serve ("survivor parity" — batched greedy
decode is row-independent, so quarantining one slot must not move a bit
in any other), failed requests end in a structured
:class:`RequestOutcome`, and the page pool ends every run — success or
error path — with ``assert_all_free`` clean.

Covered fault classes: allocator OOM (``alloc_oom``), poison requests
in prefill and decode dispatch (``prefill_dispatch`` /
``decode_dispatch``, single-victim attribution via ``target_rid``),
dispatch retry + xla-backend fallback (the degradation ladder),
deadline expiry and cooperative cancel, prefix-cache errors (cold-
prefill degradation), plan-resolution faults, scheduler stalls,
straggler ticks (watchdog), bounded-queue rejection, and SIGTERM
graceful drain (subprocess), alone and in combined mixes.
"""
import os
import signal
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax.numpy as jnp

from repro.models import model_zoo
from repro.runtime import faults as F
from repro.runtime import kv_cache as KV
from repro.runtime.batching import (ContinuousBatchingScheduler,
                                    RejectedError, RequestState,
                                    SchedulerStallError)
from repro.runtime.fault_tolerance import StepWatchdog
from repro.runtime.serve_loop import Engine

MAX_LEN = 48
PAGE = 8
CHUNK = 8
LENS = [5, 17, 8, 23, 3, 12]
MNS = [6, 3, 8, 4, 5, 7]


def _requests(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, l).astype(np.int32)
            for l in lens]


def _refs(eng, reqs, mns):
    return [np.asarray(eng.generate(jnp.asarray(r)[None], m)[0][0])
            for r, m in zip(reqs, mns)]


@pytest.fixture(scope="module")
def stablelm():
    cfg = model_zoo.reduced_config(model_zoo.get_config("stablelm-3b"))
    params = model_zoo.build(cfg)
    return cfg, Engine(cfg, params, max_len=MAX_LEN, packed=False)


def _fake_cfg():
    return model_zoo.reduced_config(model_zoo.get_config("stablelm-3b"))


class FakeEngine:
    """Duck-typed engine (scheduling logic only, no tracing) — chaos
    schedules that never need real numerics run on this."""

    def __init__(self, cfg, max_len):
        self.cfg = cfg
        self.max_len = max_len

    def prefill_chunk(self, pages, pt, lens, tokens, logit_index, *,
                      page_size):
        return jnp.zeros((), jnp.int32), pages

    def decode_step(self, pages, pt, lens, mask, last, *, page_size):
        return last, pages


def _assert_survivor_parity(outs, refs, stats, *, expect_failed=()):
    """The chaos gate: DONE requests match the fault-free reference
    bitwise; non-DONE requests carry structured outcomes; the failure
    set is exactly ``expect_failed`` when given."""
    for i, (o, r) in enumerate(zip(outs, refs)):
        oc = stats.outcomes[i]
        if oc.state == RequestState.DONE:
            np.testing.assert_array_equal(
                o, r, err_msg=f"survivor {i} diverged from fault-free run")
        else:
            assert o is None
            assert oc.error is not None
            if oc.state == RequestState.FAILED:   # fault-evicted: typed
                assert oc.error_type is not None
            assert oc.emitted < len(r)
            if oc.tokens is not None:      # salvaged partials match too
                np.testing.assert_array_equal(oc.tokens, r[:len(oc.tokens)])
    if expect_failed:
        bad = {i for i, _ in enumerate(refs)
               if stats.outcomes[i].state != RequestState.DONE}
        assert bad == set(expect_failed)


# ------------------------------------------------- injection registry
def test_fault_plan_is_deterministic_and_scoped():
    spec = F.FaultSpec("alloc_oom", p=0.5)
    seqs = []
    for _ in range(2):
        plan = F.FaultPlan(spec, seed=7)
        fired = []
        for i in range(64):
            try:
                F.maybe_fire("alloc_oom")          # no scope: no-op
                with F.use_faults(plan):
                    F.maybe_fire("alloc_oom", why="grow")
                fired.append(0)
            except F.FaultInjected:
                fired.append(1)
        seqs.append(fired)
    assert seqs[0] == seqs[1], "same seed must fire identically"
    assert 0 < sum(seqs[0]) < 64
    assert plan.fired["alloc_oom"] == sum(seqs[0])
    # outside the scope nothing ever fires
    F.maybe_fire("alloc_oom")


def test_fault_spec_occurrence_and_target_semantics():
    plan = F.FaultPlan(F.FaultSpec("decode_dispatch", at=(1,),
                                   target_rid=3))
    with F.use_faults(plan):
        # rid 3 not involved: not an eligible occurrence, no count
        F.maybe_fire("decode_dispatch", rids=(0, 1))
        F.maybe_fire("decode_dispatch", rids=(1, 3))   # occ 0: no fire
        with pytest.raises(F.FaultInjected) as ei:
            F.maybe_fire("decode_dispatch", rids=(1, 3))   # occ 1
        assert ei.value.rid == 3
        F.maybe_fire("decode_dispatch", rids=(1, 3))   # occ 2: done
    assert [e[1] for e in plan.events] == [1]
    with pytest.raises(ValueError, match="unknown injection point"):
        F.FaultSpec("not_a_point")


def test_fault_error_override_and_delay():
    plan = F.FaultPlan(
        F.FaultSpec("alloc_oom", at=(0,), error=lambda: KV.OutOfPagesError(
            "injected pool exhaustion")),
        F.FaultSpec("slow_tick", at=(0,), delay_s=0.001))
    with F.use_faults(plan):
        with pytest.raises(KV.OutOfPagesError):
            F.maybe_fire("alloc_oom")
        F.maybe_fire("slow_tick")          # sleeps, must not raise
    assert plan.fired == {"alloc_oom": 1, "slow_tick": 1}


# ------------------------------------- survivor parity, real numerics
def test_poison_prefill_quarantined(stablelm):
    """A request whose prefill dispatch always fails (retry exhausted)
    is quarantined; everyone else matches the fault-free run bitwise."""
    cfg, eng = stablelm
    reqs = _requests(cfg, LENS)
    refs = _refs(eng, reqs, MNS)
    # at=(0,1,2): primary, retry, AND the xla-fallback attempt all fail
    plan = F.FaultPlan(F.FaultSpec("prefill_dispatch", at=(0, 1, 2),
                                   target_rid=2))
    with F.use_faults(plan):
        outs, stats = eng.serve(reqs, batch_slots=2, max_new_tokens=MNS,
                                prefill_chunk=CHUNK, page_size=PAGE)
    _assert_survivor_parity(outs, refs, stats, expect_failed={2})
    assert stats.outcomes[2].error_type == "FaultInjected"
    assert stats.outcomes[2].emitted == 0
    assert stats.dispatch_retries >= 1 and stats.backend_fallbacks >= 1


def test_poison_decode_single_victim(stablelm):
    """A decode-dispatch fault attributed to one rid (the error carries
    ``.rid``) evicts only that request mid-generation — its co-batched
    neighbors keep decoding and stay bit-identical, and its own partial
    tokens are salvaged into the outcome."""
    cfg, eng = stablelm
    reqs = _requests(cfg, LENS)
    refs = _refs(eng, reqs, MNS)
    # rid 1 (max_new=3) is in exactly 2 successful decode dispatches;
    # eligible occurrence 1 is its second one, 2 and 3 the retry and
    # fallback attempts of the same tick — the full ladder fails
    plan = F.FaultPlan(F.FaultSpec("decode_dispatch", at=(1, 2, 3),
                                   target_rid=1))
    with F.use_faults(plan):
        outs, stats = eng.serve(reqs, batch_slots=3, max_new_tokens=MNS,
                                prefill_chunk=CHUNK, page_size=PAGE)
    _assert_survivor_parity(outs, refs, stats, expect_failed={1})
    oc = stats.outcomes[1]
    assert oc.state == RequestState.FAILED and oc.tokens is not None
    assert 0 < len(oc.tokens) < MNS[1]     # partial, salvaged, matching


def test_dispatch_retry_recovers(stablelm):
    """A transient dispatch fault (first attempt only) is absorbed by
    the retry: every request completes with full parity."""
    cfg, eng = stablelm
    reqs = _requests(cfg, LENS[:4])
    refs = _refs(eng, reqs, MNS[:4])
    plan = F.FaultPlan(F.FaultSpec("decode_dispatch", at=(0,)),
                       F.FaultSpec("prefill_dispatch", at=(0,)))
    with F.use_faults(plan):
        outs, stats = eng.serve(reqs, batch_slots=2,
                                max_new_tokens=MNS[:4],
                                prefill_chunk=CHUNK, page_size=PAGE)
    assert stats.completed == 4 and stats.failed == 0
    assert stats.dispatch_retries >= 2
    assert stats.backend_fallbacks == 0
    for o, r in zip(outs, refs):
        np.testing.assert_array_equal(o, r)


def test_backend_fallback_bitwise_parity(stablelm):
    """Both primary attempts fail -> the dispatch lands on the xla
    fallback step set.  All backends pass the same bit-exactness gate,
    so outputs must still match generate exactly."""
    cfg, eng = stablelm
    reqs = _requests(cfg, LENS[:4])
    refs = _refs(eng, reqs, MNS[:4])
    plan = F.FaultPlan(F.FaultSpec("decode_dispatch", at=(0, 1)))
    with F.use_faults(plan):
        outs, stats = eng.serve(reqs, batch_slots=2,
                                max_new_tokens=MNS[:4],
                                prefill_chunk=CHUNK, page_size=PAGE)
    assert stats.completed == 4
    assert stats.backend_fallbacks >= 1
    for o, r in zip(outs, refs):
        np.testing.assert_array_equal(o, r)


def test_alloc_oom_quarantines_not_crashes(stablelm):
    """An injected allocator failure mid-run fails the requesting slot
    only; survivors keep parity and the pool audits clean."""
    cfg, eng = stablelm
    reqs = _requests(cfg, LENS)
    refs = _refs(eng, reqs, MNS)
    plan = F.FaultPlan(F.FaultSpec(
        "alloc_oom", at=(4,),
        error=lambda: KV.OutOfPagesError("injected pool exhaustion")))
    with F.use_faults(plan):
        outs, stats = eng.serve(reqs, batch_slots=2, max_new_tokens=MNS,
                                prefill_chunk=CHUNK, page_size=PAGE)
    assert 0 < stats.completed < len(reqs) + 1
    assert stats.failed >= 1
    _assert_survivor_parity(outs, refs, stats)
    for oc in stats.outcomes.values():
        if oc.state == RequestState.FAILED:
            assert oc.error_type == "OutOfPagesError"


def test_deadline_expiry_under_load(stablelm):
    """A request with an expired total budget ends TIMED_OUT with a
    structured outcome; the others complete with parity."""
    cfg, eng = stablelm
    reqs = _requests(cfg, LENS[:4])
    refs = _refs(eng, reqs, MNS[:4])
    budgets = [None, 0.0, None, None]      # rid 1: already expired
    outs, stats = eng.serve(reqs, batch_slots=2, max_new_tokens=MNS[:4],
                            prefill_chunk=CHUNK, page_size=PAGE,
                            total_budget_s=budgets)
    _assert_survivor_parity(outs, refs, stats, expect_failed={1})
    assert stats.outcomes[1].state == RequestState.TIMED_OUT
    assert "budget" in stats.outcomes[1].error


def test_prefix_cache_error_degrades_to_cold_prefill(stablelm):
    """Prefix-cache faults (lookup + admit) must never fail a request:
    the scheduler serves it cold, counts the degradation, and outputs
    stay bit-identical — including for requests that WOULD have hit."""
    cfg, eng = stablelm
    rng = np.random.default_rng(3)
    pre = rng.integers(1, cfg.vocab_size, 16).astype(np.int32)
    reqs = [np.concatenate([pre, rng.integers(
                1, cfg.vocab_size, 6).astype(np.int32)])
            for _ in range(4)]
    mns = [4, 5, 3, 6]
    refs = _refs(eng, reqs, mns)
    plan = F.FaultPlan(F.FaultSpec("prefix_cache", p=0.6), seed=11)
    with F.use_faults(plan):
        outs, stats = eng.serve(reqs, batch_slots=2, max_new_tokens=mns,
                                prefill_chunk=CHUNK, page_size=PAGE,
                                prefix_cache=True)
    assert stats.completed == 4 and stats.failed == 0
    assert sum(stats.degraded.values()) >= 1
    for o, r in zip(outs, refs):
        np.testing.assert_array_equal(o, r)


def _oom():
    return KV.OutOfPagesError("injected pool exhaustion")


# Several fault classes in one run: a transient prefill failure, a
# decode fault whose whole ladder fails for rid 1, an allocator OOM, a
# straggler tick and, with the prefix cache on, failing cache lookups.
COMBINED_MIXES = {
    "combined": (
        (F.FaultSpec("prefill_dispatch", at=(0,)),
         F.FaultSpec("decode_dispatch", at=(1, 2, 3), target_rid=1),
         F.FaultSpec("alloc_oom", at=(9,), error=_oom),
         F.FaultSpec("slow_tick", at=(6,), delay_s=0.05)),
        {}),
    "combined_prefix": (
        (F.FaultSpec("prefill_dispatch", at=(0,)),
         F.FaultSpec("decode_dispatch", at=(1, 2, 3), target_rid=1),
         F.FaultSpec("prefix_cache", p=0.5)),
        {"prefix_cache": True}),
}


@pytest.mark.parametrize("mix", sorted(COMBINED_MIXES))
def test_combined_fault_mix_survivor_parity(stablelm, mix):
    """Fault classes fired together in one seeded run: every survivor
    is bit-identical to the fault-free run, every failure carries a
    structured outcome, and the pool audits clean at teardown."""
    cfg, eng = stablelm
    specs, serve_kw = COMBINED_MIXES[mix]
    reqs = _requests(cfg, LENS)
    refs = _refs(eng, reqs, MNS)
    plan = F.FaultPlan(*specs, seed=0)
    with F.use_faults(plan):
        outs, stats = eng.serve(reqs, batch_slots=3, max_new_tokens=MNS,
                                prefill_chunk=CHUNK, page_size=PAGE,
                                **serve_kw)
    _assert_survivor_parity(outs, refs, stats)
    assert set(plan.fired) == {spec.point for spec in specs}, \
        f"a fault class of the mix never fired: {dict(plan.fired)}"
    assert plan.fired["prefill_dispatch"] == 1
    assert stats.dispatch_retries >= 1 and stats.failed >= 1
    assert stats.completed + stats.failed == len(reqs)


def test_plan_resolve_fault_releases_inflight_waiters():
    """An injected failure inside gemm.plan()'s miss path must not wedge
    the in-flight dedup (the resolving owner still pops the key and
    sets the event), so a retry resolves cleanly."""
    from repro import gemm
    gemm.plan_cache_clear()
    fplan = F.FaultPlan(F.FaultSpec("plan_resolve", at=(0,)))
    with F.use_faults(fplan):
        with pytest.raises(F.FaultInjected):
            gemm.plan(128, 256, 512)
        p = gemm.plan(128, 256, 512)       # retry: clean resolve
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((128, 512)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((512, 256)), jnp.float32)
    out = np.asarray(gemm.execute(p, a, b))
    np.testing.assert_allclose(out, np.asarray(a) @ np.asarray(b),
                               rtol=2e-4, atol=2e-4)


# ------------------------------------------- scheduler-level isolation
def test_bounded_queue_rejects_with_snapshot():
    sched = ContinuousBatchingScheduler(
        FakeEngine(_fake_cfg(), MAX_LEN), batch_slots=1,
        prefill_chunk=CHUNK, page_size=PAGE, max_queue=2)
    for _ in range(2):
        sched.submit(np.arange(1, 6, dtype=np.int32), 2)
    with pytest.raises(RejectedError) as ei:
        sched.submit(np.arange(1, 6, dtype=np.int32), 2)
    snap = ei.value.snapshot
    assert snap["queue_depth"] == 2 and snap["max_queue"] == 2
    assert snap["free_pages"] == snap["num_pages"]
    assert len(sched.outcomes) == 2        # rejected request never enters


def test_cancel_queued_and_running():
    sched = ContinuousBatchingScheduler(
        FakeEngine(_fake_cfg(), MAX_LEN), batch_slots=1,
        prefill_chunk=CHUNK, page_size=PAGE)
    r0 = sched.submit(np.arange(1, 10, dtype=np.int32), 6)
    r1 = sched.submit(np.arange(1, 10, dtype=np.int32), 6)
    while not sched.slots[0].prefill_done:
        sched.step()
    assert sched.cancel(r0) and sched.cancel(r1)
    assert not sched.cancel(999)
    while sched.step():
        pass
    sched._materialize()
    assert sched.outcomes[r0].state == RequestState.CANCELLED  # running
    assert sched.outcomes[r1].state == RequestState.CANCELLED  # queued
    assert sched.outcomes[r1].emitted == 0
    assert sched.kv.free_count == sched.kv.num_pages
    sched.kv.assert_all_free()


def test_deadlines_with_fake_clock():
    """Deterministic deadline semantics on an injected clock: TTFT
    budget trips only before the first token, total budget any time."""
    clk = [0.0]
    sched = ContinuousBatchingScheduler(
        FakeEngine(_fake_cfg(), MAX_LEN), batch_slots=2,
        prefill_chunk=CHUNK, page_size=PAGE, clock=lambda: clk[0])
    r0 = sched.submit(np.arange(1, 20, dtype=np.int32), 8,
                      ttft_budget_s=10.0)   # generous: never trips
    r1 = sched.submit(np.arange(1, 20, dtype=np.int32), 8,
                      total_budget_s=0.5)   # trips after first ticks
    while sched.step():
        clk[0] += 0.3
    sched._materialize()
    assert sched.outcomes[r0].state == RequestState.DONE
    assert sched.outcomes[r1].state == RequestState.TIMED_OUT
    assert "total budget" in sched.outcomes[r1].error
    sched.kv.assert_all_free()


def test_scheduler_stall_is_diagnosable(monkeypatch):
    """A wedged scheduler surfaces SchedulerStallError (a RuntimeError,
    preserving the old contract) with a state snapshot, and the
    exception path still releases every page."""
    sched = ContinuousBatchingScheduler(
        FakeEngine(_fake_cfg(), MAX_LEN), batch_slots=1,
        prefill_chunk=CHUNK, page_size=PAGE)
    monkeypatch.setattr(sched, "_admit", lambda: None)   # never admits
    with pytest.raises(SchedulerStallError, match="no progress") as ei:
        sched.run([np.arange(1, 6, dtype=np.int32)], 2)
    assert isinstance(ei.value, RuntimeError)
    assert ei.value.snapshot["queue_depth"] == 1
    assert sched.outcomes[0].state == RequestState.CANCELLED
    assert sched.kv.free_count == sched.kv.num_pages


def test_run_exception_exit_releases_pages(monkeypatch):
    """Satellite 1: an exception escaping the tick loop still evicts
    live slots, drains the queue to outcomes, and passes the
    assert_all_free audit (the try/finally around run())."""
    sched = ContinuousBatchingScheduler(
        FakeEngine(_fake_cfg(), MAX_LEN), batch_slots=1,
        prefill_chunk=CHUNK, page_size=PAGE)

    def boom():
        raise ZeroDivisionError("scheduler bug")
    monkeypatch.setattr(sched, "_decode_step", boom)
    with pytest.raises(ZeroDivisionError):
        sched.run([np.arange(1, 6, dtype=np.int32),
                   np.arange(1, 40, dtype=np.int32)], [2, 2])
    states = {r: o.state for r, o in sched.outcomes.items()}
    assert states[0] == RequestState.FAILED        # was live in a slot
    assert "run aborted" in sched.outcomes[0].error
    assert sched.kv.free_count == sched.kv.num_pages
    sched.kv.assert_all_free()     # would raise on a refcount leak


def test_slow_tick_error_cleans_up():
    """An error spec on the tick boundary aborts the run through the
    same quarantine path — structured outcomes, clean pool."""
    sched = ContinuousBatchingScheduler(
        FakeEngine(_fake_cfg(), MAX_LEN), batch_slots=2,
        prefill_chunk=CHUNK, page_size=PAGE)
    plan = F.FaultPlan(F.FaultSpec("slow_tick", at=(3,),
                                   error=RuntimeError("tick bomb")))
    with F.use_faults(plan):
        with pytest.raises(RuntimeError, match="tick bomb"):
            sched.run([np.arange(1, 20, dtype=np.int32)] * 3, 6)
    assert all(o.state in (RequestState.FAILED, RequestState.CANCELLED)
               for o in sched.outcomes.values())
    assert sched.kv.free_count == sched.kv.num_pages


# ----------------------------------------------------------- watchdog
def test_watchdog_flags_injected_straggler_tick():
    """Satellite 2: a delay-injected tick lands in ServeStats.stragglers.
    Factor 8 over sub-millisecond stub ticks vs a 60ms injected delay
    keeps this deterministic without flagging warmup."""
    sched = ContinuousBatchingScheduler(
        FakeEngine(_fake_cfg(), MAX_LEN), batch_slots=2,
        prefill_chunk=CHUNK, page_size=PAGE, watchdog_factor=8.0)
    plan = F.FaultPlan(F.FaultSpec("slow_tick", at=(8,), delay_s=0.06))
    with F.use_faults(plan):
        _, stats = sched.run([np.arange(1, 20, dtype=np.int32)] * 4, 8)
    assert stats.completed == 4
    assert len(stats.stragglers) >= 1
    assert max(ev.dt for ev in stats.stragglers) >= 0.06


def test_watchdog_warmup_never_flags():
    wd = StepWatchdog(factor=3.0, warmup=2)
    assert wd.record(10.0) is False        # warmup observed, not flagged
    assert wd.record(10.0) is False
    assert wd.record(11.0) is False        # in family with the EMA
    assert wd.record(100.0) is True        # genuine straggler
    assert len(wd.events) == 1


# -------------------------------------------------- graceful shutdown
_SHUTDOWN_SCRIPT = textwrap.dedent("""
    import sys
    import numpy as np
    import jax.numpy as jnp
    from repro.models import model_zoo
    from repro.runtime import faults as F
    from repro.runtime.batching import (ContinuousBatchingScheduler,
                                        RequestState)
    from repro.runtime.fault_tolerance import GracefulShutdown

    class FakeEngine:
        def __init__(self, cfg, max_len):
            self.cfg, self.max_len = cfg, max_len
        def prefill_chunk(self, pages, pt, lens, tokens, li, *,
                          page_size):
            return jnp.zeros((), jnp.int32), pages
        def decode_step(self, pages, pt, lens, mask, last, *, page_size):
            return last, pages

    cfg = model_zoo.reduced_config(model_zoo.get_config("stablelm-3b"))
    gs = GracefulShutdown().install()
    sched = ContinuousBatchingScheduler(
        FakeEngine(cfg, 48), batch_slots=2, prefill_chunk=8,
        page_size=8, shutdown=gs)
    for _ in range(40):
        sched.submit(np.arange(1, 12, dtype=np.int32), 6)
    # announce READY only once a request has finished, so the parent's
    # SIGTERM always lands mid-stream with completions on the books;
    # slow ticks keep the run alive long past the signal
    plan = F.FaultPlan(F.FaultSpec("slow_tick", delay_s=0.02))
    ready = False
    with F.use_faults(plan):
        while sched.step():
            if not ready and sched.stats.completed >= 1:
                print("READY", flush=True)
                ready = True
    sched._materialize()
    assert gs.requested, "SIGTERM never observed"
    done = sum(1 for o in sched.outcomes.values()
               if o.state == RequestState.DONE)
    cancelled = [o for o in sched.outcomes.values()
                 if o.state == RequestState.CANCELLED]
    assert done > 0, "drain must finish in-flight requests"
    assert cancelled, "drain must cancel the queue"
    assert all(o.error == "shutdown" for o in cancelled)
    assert sched.kv.free_count == sched.kv.num_pages
    sched.kv.assert_all_free()
    print(f"DRAINED done={done} cancelled={len(cancelled)}", flush=True)
""")


@pytest.mark.skipif(sys.platform == "win32", reason="POSIX signals")
def test_graceful_shutdown_drains_on_sigterm(tmp_path):
    """Satellite 3, end to end in a subprocess: SIGTERM mid-run finishes
    in-flight requests, cancels queued ones with structured outcomes,
    and exits 0 inside the grace window."""
    script = tmp_path / "serve_victim.py"
    script.write_text(_SHUTDOWN_SCRIPT)
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.Popen([sys.executable, str(script)],
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, env=env, text=True)
    try:
        assert proc.stdout.readline().strip() == "READY"
        proc.send_signal(signal.SIGTERM)
        out, _ = proc.communicate(timeout=120)
    except Exception:
        proc.kill()
        raise
    assert proc.returncode == 0, f"victim failed:\n{out}"
    assert "DRAINED done=" in out
