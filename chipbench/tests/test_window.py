"""The window's arithmetic: rates over all work and all time, tails over
every request and every gap, and a stall inside the window shows."""
import math

import pytest

from chipbench import window as W


def _serve(stall_at=None, stall_s=0.0, n_req=40, gap=0.25, tick=0.02,
           prompt_len=100, out=10):
    """A synthetic open-loop record: a request due every ``gap`` seconds,
    its prompt prefilled in one tick, then one token per tick; a stall of
    ``stall_s`` at time ``stall_at`` delays every tick after it."""
    rec = W.Recorder(lambda rem: 128)
    rec.w0, rec.w1 = 1.0, 9.0

    def late(t):
        return t + (stall_s if stall_at is not None and t >= stall_at else 0)

    for rid in range(n_req):
        due = rid * gap
        rec.submitted(rid, due=due, t=due, prompt_len=prompt_len,
                      max_new=out)
        t = late(due + tick)
        rec.observe([("prefill", rid, 0, 0, prompt_len)], t)
        for k in range(1, out):
            rec.observe([("decode", (rid,))], late(due + tick * (k + 1)))
        rec.observe([("finish", rid, 0)], late(due + tick * out))
    return rec


def test_rates_are_all_work_over_all_window_time():
    rec = _serve()
    inside_first = [r for r in rec.reqs.values() if rec.inside(r.first)]
    assert W.prompt_tok_s(rec) == pytest.approx(
        100 * len(inside_first) / 8.0)
    n_tok = sum(1 for r in rec.reqs.values() for t in r.emissions
                if 1.0 < t <= 9.0)
    assert W.out_tok_s(rec) == pytest.approx(n_tok / 8.0)
    # a window twice as long with the same work halves the rate
    rec.w1 = 17.0
    assert W.out_tok_s(rec) == pytest.approx(
        sum(1 for r in rec.reqs.values() for t in r.emissions
            if 1.0 < t <= 17.0) / 16.0)


def test_tails_are_taken_over_every_request_and_gap():
    rec = _serve()
    ttft = W.ttft_samples(rec)
    assert len(ttft) == len([r for r in rec.reqs.values()
                             if 1.0 <= r.due < 9.0])
    assert all(v == pytest.approx(0.02) for v in ttft)
    itl = W.itl_samples(rec)
    # every gap whose later token lies inside the window, all requests
    assert len(itl) == sum(1 for r in rec.reqs.values()
                           for a, b in zip(r.emissions, r.emissions[1:])
                           if 1.0 < b <= 9.0)
    # a request due in the window that never got a token reads infinite
    rec.submitted(99, due=8.5, t=8.5, prompt_len=10, max_new=2)
    assert math.isinf(max(W.ttft_samples(rec)))
    assert W.percentile([], 95) is None


def test_a_stall_inside_the_window_moves_rate_and_tails():
    base = _serve()
    stalled = _serve(stall_at=5.1, stall_s=0.5)
    assert W.out_tok_s(stalled) < W.out_tok_s(base)
    assert max(W.itl_samples(stalled)) == pytest.approx(0.52)
    assert W.percentile(W.ttft_samples(stalled), 100) > \
        W.percentile(W.ttft_samples(base), 100)
    # a stall before the window (in the ramp) does not touch its tails
    early = _serve(stall_at=0.3, stall_s=0.2, n_req=2)
    assert all(g < 0.1 for g in W.itl_samples(early))


def test_prefill_dispatches_record_width_and_contexts():
    rec = W.Recorder(lambda rem: 128 if rem >= 128 else 32)
    rec.w0, rec.w1 = 0.0, 1.0
    rec.submitted(0, due=0.0, t=0.0, prompt_len=150, max_new=3)
    rec.observe([("prefill", 0, 0, 0, 128)], 0.1)
    rec.observe([("prefill", 0, 0, 128, 150)], 0.2)
    rec.observe([("decode", (0,))], 0.3)
    p1, p2, d = rec.dispatches
    assert (p1.width, p2.width) == (128, 32)
    assert d.contexts == (151,)       # prompt + the first token
    assert rec.reqs[0].first == 0.2
