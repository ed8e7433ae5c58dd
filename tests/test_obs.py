"""Observability-layer gates (ISSUE 9).

Four contracts:

* **Non-perturbation** — serving with the full obs stack active
  (tracer + flight recorder + metrics) returns bit-identical tokens to
  the un-instrumented per-request ``generate`` reference, and the
  inactive instrumentation adds no gross overhead to the eager GEMM
  path.
* **Schema** — an exported trace is valid Chrome-trace JSON
  (``validate_chrome_trace`` finds nothing) and its synthesized
  ``gemm_dispatch`` spans carry plan key and lever, and no GFLOPS.
* **Determinism** — two identical seeded serve runs publish
  byte-identical metrics snapshots once wall-clock-valued metrics
  (``_ms`` / ``_seconds`` names) are excluded.
* **Bounded state** — the flight-recorder ring wraps (oldest first),
  the tracer drops oldest past its cap, and the scheduler's audit
  trace is bounded with a ``trace_dropped`` counter.
"""
import json

import numpy as np
import pytest

import jax.numpy as jnp

from repro import gemm, obs
from repro.models import model_zoo
from repro.obs import metrics as obs_metrics
from repro.obs import recorder as obs_recorder
from repro.obs import spans as obs_spans
from repro.runtime.batching import _BoundedTrace
from repro.runtime.serve_loop import Engine

MAX_LEN = 48
PAGE = 8
CHUNK = 8
LENS = [5, 17, 8, 12]
MNS = [6, 3, 8, 5]


def _requests(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, l).astype(np.int32)
            for l in lens]


@pytest.fixture(scope="module")
def engine():
    cfg = model_zoo.reduced_config(model_zoo.get_config("stablelm-3b"))
    params = model_zoo.build(cfg)
    return cfg, Engine(cfg, params, max_len=MAX_LEN, packed=True)


# ------------------------------------------------------ non-perturbation
def test_serve_parity_with_full_obs_active(engine):
    """Tokens with tracer + recorder + metrics all on == un-instrumented
    per-request generate."""
    cfg, eng = engine
    reqs = _requests(cfg, LENS)
    refs = [np.asarray(eng.generate(jnp.asarray(r)[None], m)[0][0])
            for r, m in zip(reqs, MNS)]
    tracer = obs.Tracer()
    rec = obs.FlightRecorder(fence=True)
    reg = obs.MetricsRegistry()
    reg.add_collector(obs.gemm_collector)
    with obs.use_tracer(tracer), obs.use_recorder(rec), \
            obs.use_metrics(reg):
        outs, stats = eng.serve(reqs, batch_slots=2, max_new_tokens=MNS,
                                prefill_chunk=CHUNK, page_size=PAGE,
                                sync_per_step=True)
    for i, (o, r) in enumerate(zip(outs, refs)):
        np.testing.assert_array_equal(
            o, r, err_msg=f"request {i} diverged under observation")
    # the run actually WAS observed
    assert tracer.events, "no spans collected"
    assert rec.traced > 0 or rec.total > 0, "recorder saw nothing"
    snap = reg.snapshot()
    assert snap["serve_decode_tokens"]["series"]["_"] == sum(MNS)
    assert snap["serve_prefill_tokens"]["series"]["_"] == sum(LENS)
    assert stats.trace_dropped == 0


def test_inactive_obs_overhead_bounded():
    """With no tracer/recorder/metrics active, the execute() hook is one
    int check — eager dispatch time must not regress grossly.  A
    generous 1.5x bound with retry-on-noise: this is a CPU timing, so it
    guards against a gross regression, never measures device speed."""
    import importlib
    exec_mod = importlib.import_module("repro.gemm.execute")
    assert obs_recorder._HOT == 0 and obs_spans._ANY == 0
    rng = np.random.default_rng(0)
    p = gemm.plan(64, 256, 256)
    x = jnp.asarray(rng.standard_normal((64, 256)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((256, 256)), jnp.float32)
    gemm.execute(p, x, w)                 # compile both paths
    exec_mod._execute_impl(p, x, w)

    def best(fn, reps):
        return obs.measure(fn, fence=True, repeats=reps)

    for attempt in range(4):
        reps = 10 * (attempt + 1)
        t_hook = best(lambda: gemm.execute(p, x, w), reps)
        t_bare = best(lambda: exec_mod._execute_impl(p, x, w), reps)
        if t_hook <= t_bare * 1.5:
            return
    pytest.fail(f"inactive obs hook overhead: execute {t_hook * 1e6:.1f}us"
                f" vs bare {t_bare * 1e6:.1f}us")


# ----------------------------------------------------------------- schema
def test_exported_trace_is_valid_and_carries_gemm_spans(engine, tmp_path):
    cfg, eng = engine
    reqs = _requests(cfg, LENS, seed=1)
    tracer = obs.Tracer()
    rec = obs.FlightRecorder()
    with obs.use_tracer(tracer), obs.use_recorder(rec):
        eng.serve(reqs, batch_slots=2, max_new_tokens=MNS,
                  prefill_chunk=CHUNK, page_size=PAGE)
    path = tracer.export_chrome_trace(str(tmp_path / "t.json"),
                                      recorder=rec)
    trace = json.load(open(path))
    assert obs.validate_chrome_trace(trace) == []
    # jitted steps registered manifests; the exporter synthesized
    # apportioned per-GEMM children under the tick spans
    assert trace["gemmManifests"], "no step manifests in trace"
    gemms = obs.gemm_events(trace)
    assert gemms, "no gemm_dispatch spans synthesized"
    for a in gemms[:10]:
        assert a["plan"] and a["lever"] and a["m"] > 0
        assert a["apportioned"] is True
        # a duration split by the plans' predicted shares is no
        # measurement: no rate or roofline share is derived from it
        assert "gflops" not in a and "roofline_frac" not in a
    # tick spans carry the step attr linking them to their manifest
    # (plan_resolve spans only appear when plans were not already
    # cached by an earlier run — not asserted here)
    names = {ev["name"] for ev in trace["traceEvents"]}
    assert {"prefill_chunk", "decode_tick"} <= names
    rows = obs.per_shape_table(trace)
    assert rows and all(r["dispatches"] > 0 for r in rows)
    assert any("fine_panels" in r["lever_mix"] or
               "prepack" in r["lever_mix"] for r in rows)


def test_validate_chrome_trace_catches_bad_events():
    assert obs.validate_chrome_trace({}) == ["missing traceEvents key"]
    bad = {"traceEvents": [
        {"name": "a", "ph": "Z", "ts": 0},               # bad phase
        {"name": "b", "ph": "X", "ts": 0},               # missing dur
        {"ph": "i", "ts": 0},                            # missing name
        {"name": "c", "ph": "X", "ts": "soon", "dur": 1},  # bad ts
    ]}
    assert len(obs.validate_chrome_trace(bad)) == 4
    good = {"traceEvents": [
        {"name": "a", "ph": "X", "ts": 0.0, "dur": 1.0, "args": {}},
        {"name": "m", "ph": "M", "args": {"name": "p"}},
        {"name": "i", "ph": "i", "ts": 2.0},
    ]}
    assert obs.validate_chrome_trace(good) == []


# ------------------------------------------------------------ determinism
def _strip_timing(snap):
    return {k: v for k, v in snap.items()
            if not (k.endswith("_ms") or k.endswith("_seconds"))}


def test_metrics_snapshot_deterministic_across_identical_runs(engine):
    cfg, eng = engine
    snaps = []
    for _ in range(2):
        reqs = _requests(cfg, LENS, seed=7)
        reg = obs.MetricsRegistry()      # fresh registry, no collectors
        with obs.use_metrics(reg):
            eng.serve(reqs, batch_slots=2, max_new_tokens=MNS,
                      prefill_chunk=CHUNK, page_size=PAGE)
        snaps.append(json.dumps(_strip_timing(reg.snapshot()),
                                sort_keys=True))
    assert snaps[0] == snaps[1], "identical runs published different " \
                                 "non-timing metrics"


def test_prometheus_text_and_histogram_buckets():
    reg = obs.MetricsRegistry()
    reg.counter("reqs_total", help="requests").inc(3, state="DONE")
    reg.gauge("depth").set(4)
    h = reg.histogram("lat_ms", buckets=(1, 10, 100))
    for v in (0.5, 5, 5, 50, 500):
        h.observe(v)
    text = reg.prometheus_text()
    assert '# TYPE reqs_total counter' in text
    assert 'reqs_total{state="DONE"} 3' in text
    assert 'depth 4' in text
    # cumulative buckets: <=1:1, <=10:3, <=100:4, +Inf:5
    assert 'lat_ms_bucket{le="1.0"} 1' in text
    assert 'lat_ms_bucket{le="10.0"} 3' in text
    assert 'lat_ms_bucket{le="100.0"} 4' in text
    assert 'lat_ms_bucket{le="+Inf"} 5' in text
    assert 'lat_ms_count 5' in text
    snap = reg.snapshot()
    assert snap["lat_ms"]["series"]["_"]["counts"] == [1, 2, 1, 1]
    assert snap["lat_ms"]["series"]["_"]["count"] == 5
    with pytest.raises(TypeError):
        reg.gauge("reqs_total")          # kind collision is an error


# --------------------------------------------------------- bounded state
def test_flight_recorder_ring_wraparound():
    p = gemm.plan(8, 64, 64)
    rec = obs.FlightRecorder(capacity=4)
    for i in range(10):
        rec.record(p, 8, wall_s=1e-3 * (i + 1), fenced=True)
    assert rec.total == 10
    assert rec.wrapped == 6
    dump = rec.dump()
    assert len(dump) == 4
    ts = [r["ts_ms"] for r in dump]
    assert ts == sorted(ts), "dump not chronological"
    # the survivors are the newest four
    assert [r["wall_ms"] for r in dump] == pytest.approx([7, 8, 9, 10])
    # plan-cache proxy: first sighting is a miss, repeats are hits
    assert dump[0]["plan_cache_hit"] is True   # key seen before wrap
    assert all(r["gflops"] > 0 for r in dump)
    assert all(r["fenced"] for r in dump)


def test_recorder_records_eager_dispatches_with_lever_fields():
    rng = np.random.default_rng(1)
    p = gemm.plan(16, 64, 64)
    x = jnp.asarray(rng.standard_normal((16, 64)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((64, 64)), jnp.float32)
    rec = obs.FlightRecorder(fence=True)
    with obs.use_recorder(rec):
        gemm.execute(p, x, w)
        gemm.execute(p, x, w)
    assert rec.total == 2
    a, b = rec.dump()
    assert a["plan_cache_hit"] is False and b["plan_cache_hit"] is True
    for r in (a, b):
        assert r["m"] == 16 and r["n"] == 64 and r["k"] == 64
        assert r["backend"] and r["lever"]
        assert r["fenced"] and r["gflops"] > 0
        # roofline share only against a device the peaks table lists;
        # the CPU has none, so the share is "not measured"
        from repro.roofline import device_peaks
        if device_peaks() is None:
            assert r["roofline_frac"] is None
        else:
            assert 0 < r["roofline_frac"] <= 1


def test_tracer_drops_oldest_past_cap():
    tr = obs.Tracer(max_events=10)
    with obs.use_tracer(tr):
        for i in range(25):
            obs.instant("e", i=i)
    assert len(tr.events) <= 10 and tr.dropped > 0
    kept = [ev["args"]["i"] for ev in tr.events]
    assert kept == sorted(kept) and kept[-1] == 24   # newest survive


def test_scheduler_trace_bounded_with_drop_counter():
    t = _BoundedTrace(cap=8)
    for i in range(20):
        t.append(("ev", i))
    assert len(t) == 8
    assert t.dropped == 12
    assert [ev[1] for ev in t] == list(range(12, 20))
    assert t[0] == ("ev", 12) and t[-1] == ("ev", 19)
    assert t[2:4] == [("ev", 14), ("ev", 15)]


# ------------------------------------------------------- scoping / timer
def test_span_scoping_and_noop_handles():
    assert obs_spans.active_tracer() is None
    with obs.span("outside") as h:
        h.set(x=1)                        # noop handle, no tracer
    tr = obs.Tracer()
    with obs.use_tracer(tr):
        with obs.span("a", k=1) as h:
            h.set(post=2)
            with obs.no_tracer():
                with obs.span("shadowed"):
                    pass
            assert obs.current_span() is h
    names = [ev["name"] for ev in tr.events]
    assert names == ["a"]
    assert tr.events[0]["args"] == {"k": 1, "post": 2}


def test_fenced_timer_reports_fence_cost():
    from repro.obs.timing import FencedTimer
    y = jnp.ones((8, 8)) @ jnp.ones((8, 8))
    with FencedTimer(fence=False) as t:
        t.fence(y)
    assert not t.fenced and t.synced == 0 and t.elapsed_s >= 0
    with FencedTimer(fence=True) as t:
        t.fence(y)
    assert t.fenced and t.synced == 1


def test_gemm_roofline_bound_monotone_in_format():
    from repro.roofline import V5E, gemm_roofline
    t32 = gemm_roofline(256, 1024, 1024, weight_format="fp32", hw=V5E)
    t8 = gemm_roofline(256, 1024, 1024, weight_format="int8", hw=V5E)
    t2 = gemm_roofline(256, 1024, 1024, weight_format="ternary", hw=V5E)
    assert t32 > 0 and t32 >= t8 >= t2    # fewer weight bytes, lower bound


# ------------------------------------------------ profiler sink (spans
# on the device trace's clock)
SCHED_NESTING = {
    # span -> the span it nests in directly
    "deadlines": "sched_step", "admit": "sched_step",
    "prefill_prep": "sched_step", "prefill_chunk": "sched_step",
    "prefill_post": "sched_step", "decode_prep": "sched_step",
    "decode_tick": "sched_step", "decode_post": "sched_step",
}
PHASE_CHILDREN = ("dispatch", "fence")    # of prefill_chunk / decode_tick


def _profiled_serve(eng, reqs, tmp_path, tracer):
    import glob
    import os
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with obs.use_tracer(tracer):
            outs, _ = eng.serve(reqs, batch_slots=2, max_new_tokens=MNS,
                                prefill_chunk=CHUNK, page_size=PAGE,
                                sync_per_step=True)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[0]
    pd = jax.profiler.ProfileData.from_file(path)
    spans = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    stats = dict(e.stats)
                    if "span_id" in stats:
                        spans.append((e.name, e.start_ns, e.end_ns, stats))
    return outs, spans


def _parent(spans, child):
    """The shortest span that contains ``child`` (its direct parent)."""
    _, a, b, _ = child
    cover = [s for s in spans if s is not child and s[1] <= a
             and b <= s[2] and (s[2] - s[1]) >= (b - a)]
    return min(cover, key=lambda s: s[2] - s[1])[0] if cover else None


def test_profiler_sink_writes_scheduler_spans_in_their_nesting(engine,
                                                               tmp_path):
    cfg, eng = engine
    reqs = _requests(cfg, LENS, seed=3)
    tracer = obs.Tracer(sink="profiler")
    _, spans = _profiled_serve(eng, reqs, tmp_path, tracer)
    assert tracer.events == []                 # the sink keeps nothing
    names = {s[0] for s in spans}
    assert {"sched_step", *SCHED_NESTING, *PHASE_CHILDREN} <= names
    assert len({s[3]["span_id"] for s in spans}) == len(spans)
    for s in spans:
        if s[0] in SCHED_NESTING:
            assert _parent(spans, s) == SCHED_NESTING[s[0]], s[:3]
        elif s[0] in PHASE_CHILDREN:
            assert _parent(spans, s) in ("prefill_chunk", "decode_tick")
        elif s[0] == "sched_step":
            assert _parent(spans, s) is None
    chunk = next(s for s in spans if s[0] == "prefill_chunk")
    assert {"step", "rid", "slot", "tokens", "fenced"} <= set(chunk[3])


def test_served_tokens_identical_with_profiler_sink_on_and_off(engine,
                                                               tmp_path):
    cfg, eng = engine
    reqs = _requests(cfg, LENS, seed=4)
    off, _ = eng.serve(reqs, batch_slots=2, max_new_tokens=MNS,
                       prefill_chunk=CHUNK, page_size=PAGE,
                       sync_per_step=True)
    on, spans = _profiled_serve(eng, reqs, tmp_path,
                                obs.Tracer(sink="profiler"))
    assert spans
    for a, b in zip(off, on):
        np.testing.assert_array_equal(a, b)


def test_memory_sink_keeps_the_scheduler_spans_and_no_tracer_records_none(
        engine):
    cfg, eng = engine
    reqs = _requests(cfg, LENS[:2], seed=5)
    idle = obs.Tracer()                        # made, never installed
    assert obs_spans._ANY == 0
    eng.serve(reqs, batch_slots=2, max_new_tokens=MNS[:2],
              prefill_chunk=CHUNK, page_size=PAGE)
    assert idle.events == [] and obs.current_span() is None
    tr = obs.Tracer()
    with obs.use_tracer(tr):
        eng.serve(reqs, batch_slots=2, max_new_tokens=MNS[:2],
                  prefill_chunk=CHUNK, page_size=PAGE)
    evs = [ev for ev in tr.events if ev["ph"] == "X"]
    names = {ev["name"] for ev in evs}
    assert {"sched_step", *SCHED_NESTING, *PHASE_CHILDREN} <= names
    steps = [ev for ev in evs if ev["name"] == "sched_step"]
    for ev in evs:
        if ev["name"] in SCHED_NESTING:     # inside some sched_step
            assert any(s["ts"] <= ev["ts"] and
                       ev["ts"] + ev["dur"] <= s["ts"] + s["dur"]
                       for s in steps), ev["name"]
    with pytest.raises(ValueError):
        obs.Tracer(sink="file")


def test_serve_stats_record_steps_uploads_and_token_times(engine):
    cfg, eng = engine
    reqs = _requests(cfg, LENS, seed=6)
    reg = obs.MetricsRegistry()
    with obs.use_metrics(reg):
        outs, stats = eng.serve(reqs, batch_slots=2, max_new_tokens=MNS,
                                prefill_chunk=CHUNK, page_size=PAGE,
                                sync_per_step=True)
    n_prefill, n_decode = len(stats.prefill_tick_ms), stats.decode_dispatches
    assert stats.steps and stats.step_ms == [r.wall_ms for r in stats.steps]
    # a prefill ships table, lengths, chunk and row index; a decode
    # ships table, lengths and the write mask
    assert stats.uploads == 4 * n_prefill + 3 * n_decode
    t0s = [r.t0 for r in stats.steps]
    assert t0s == sorted(t0s)
    for r in stats.steps:
        assert 0 <= r.fence_ms <= r.wall_ms
    assert sum(r.fence_ms for r in stats.steps) > 0
    for req in stats.requests:
        assert len(req.emit_s) == req.new_tokens
        assert req.emit_s[0] == pytest.approx(req.ttft_s)
        assert len(req.itl_s) == req.new_tokens - 1
        assert all(g >= 0 for g in req.itl_s)
    snap = reg.snapshot()
    assert snap["serve_uploads"]["series"]["_"] == stats.uploads
    assert snap["serve_steps"]["series"]["_"] == len(stats.steps)
    assert snap["serve_step_ms"]["series"]["_"]["count"] == len(stats.steps)
    assert snap["serve_itl_ms"]["series"]["_"]["count"] == \
        sum(m - 1 for m in MNS)


def test_trace_report_medians_read_measured_records_only():
    rec = {"plan": "p", "m": 8, "n": 64, "k": 64, "lever": "prepack",
           "weight_format": "fp32"}
    trace = {"traceEvents": [
        {"name": "gemm_dispatch", "ph": "X", "ts": 0, "dur": 5,
         "args": dict(rec, apportioned=True)},
        {"name": "gemm_dispatch", "ph": "X", "ts": 5, "dur": 5,
         "args": dict(rec, m=16, fenced=False, wall_ms=1.0)},
        {"name": "gemm_dispatch", "ph": "X", "ts": 10, "dur": 5,
         "args": dict(rec, m=16, fenced=True, gflops=3.0,
                      roofline_frac=0.5)},
    ]}
    rows = {r["m"]: r for r in obs.per_shape_table(trace)}
    assert rows[8]["median_gflops"] is None and rows[8]["apportioned"] == 1
    assert rows[16]["median_gflops"] == 3.0
    assert rows[16]["median_roofline_frac"] == 0.5
    table = obs.format_table(obs.per_shape_table(trace))
    assert "—" in table.splitlines()[2]         # m=8: not measured


@pytest.mark.parametrize("backend,share", [("interpret", 1.0), ("xla", 0.0)])
def test_manifest_counts_stacked_weights_read_in_place(engine, backend,
                                                       share, tmp_path,
                                                       capsys):
    """Each traced dispatch records whether its weight came as a stacked
    pack and whether the backend read that layer in place: every layer
    projection of a step on the kernel (``interpret``), none on ``xla``,
    which slices the layer out first; the LM head is no stacked pack.
    ``launch/trace_report`` prints the share per step."""
    import jax
    from repro.launch import trace_report
    from repro.models import transformer
    cfg, eng = engine
    key = f"in_place_{backend}"
    tokens = jnp.asarray(_requests(cfg, [6])[0][None])
    with gemm.use_backend(backend), obs.manifest_scope(key):
        jax.eval_shape(lambda p, t: transformer.forward(
            cfg, p, t, mode="prefill"), eng.params, tokens)
    recs = obs.manifests()[key]
    stacked = [r for r in recs if r["stacked"]]
    assert len(stacked) == 4          # wqkv, wo, gate/up, down
    assert [r for r in recs if not r["stacked"]][0]["n"] == cfg.vocab_size
    assert obs.stacked_in_place_share(recs) == share
    trace = {"traceEvents": [], "gemmManifests": {key: recs}}
    path = tmp_path / "t.json"
    path.write_text(json.dumps(trace))
    trace_report.main([str(path)])
    printed = capsys.readouterr().out.split("read in place, per step:")[1]
    assert f"{key:<24} {100 * share:6.1f} %" in printed
