"""Request-level tracing, gauges, flight recorder, latency attribution
(repro.serving.observability + the SchedulerMetrics tracing bridge).

The tentpole contracts: the ring buffer is bounded and ordered, the
Chrome trace-event export is schema-valid and loads the way Perfetto
expects, a traced serving run emits a *closed* ADMIT -> QUEUED ->
PREFILL -> DECODE -> FINISH chain per completed request (KV_TRANSFER
spans appear exactly on the disaggregated backend), and tracing is
invisible to the tokens — traced and untraced runs produce identical
outputs.  Plus the metrics satellites: rejected-queue accounting,
phase attribution, per-model TTFT/ITL, distinct reservoir seeds, and
mid-run / restart elapsed semantics."""
import asyncio
import json

import jax
import numpy as np
import pytest

from repro.configs.base import LayerSpec, ModelConfig
from repro.models import transformer as tf
from repro.serving.backend import (DisaggregatedBackend, InProcessBackend,
                                   ModelBackend)
from repro.serving.engine import Engine, ServeConfig
from repro.serving.observability import (NULL_TRACER, Tracer,
                                         backend_track, request_track,
                                         sample_gauges,
                                         validate_chrome_trace)
from repro.serving.scheduler import (PagedLLMConfig, PagedLLMScheduler,
                                     Request, SamplingParams,
                                     SchedulerMetrics)

PS = 4          # page size everywhere here


def tiny_config() -> ModelConfig:
    return ModelConfig(name="obs-tiny", arch_type="dense", num_layers=2,
                       d_model=32, d_ff=64, vocab_size=64, num_heads=4,
                       num_kv_heads=2, head_dim=8, compute_dtype="float32",
                       param_dtype="float32", kv_cache_dtype="float32")


@pytest.fixture(scope="module")
def model():
    cfg = tiny_config()
    return cfg, tf.init_params(cfg, jax.random.key(0))


def make_backend(model, kind) -> ModelBackend:
    cfg, params = model
    if kind == "inproc":
        eng = Engine(cfg, params, ServeConfig(max_len=64))
        eng.init_paged(num_pages=40, page_size=PS, decode_batch=4)
        return InProcessBackend(eng)
    return DisaggregatedBackend.build(
        cfg, params, ServeConfig(max_len=64), num_pages=40, page_size=PS,
        decode_batch=4, prefill_pages=32)


def prompt_of(n, fold=0):
    return np.asarray(jax.random.randint(jax.random.fold_in(
        jax.random.key(5), fold), (n,), 0, tiny_config().vocab_size))


def fake_clock(t=0.0):
    state = {"t": t}

    def clock():
        state["t"] += 0.001
        return state["t"]
    return clock


# ===========================================================================
# Ring buffer + export schema
# ===========================================================================

def test_ring_is_bounded_ordered_and_counts_drops():
    tr = Tracer(capacity=4, clock=fake_clock())
    for i in range(10):
        tr.instant(f"ev{i}")
    evs = tr.events()
    assert len(evs) == 4
    assert [ev[0] for ev in evs] == sorted(ev[0] for ev in evs)
    assert [ev[2] for ev in evs] == ["ev6", "ev7", "ev8", "ev9"]
    st = tr.stats()
    assert st["recorded"] == 10 and st["dropped"] == 6
    assert st["capacity"] == 4


def test_events_since_filters_by_timestamp():
    tr = Tracer(capacity=16, clock=fake_clock())
    tr.instant("old", t=1.0)
    tr.instant("new", t=5.0)
    assert [ev[2] for ev in tr.events(since=2.0)] == ["new"]


def test_null_tracer_is_disabled_noop():
    assert NULL_TRACER.enabled is False
    # every call is a no-op — no ring, no exceptions, nothing recorded
    NULL_TRACER.span("s", "a/b", 0.0, 1.0)
    NULL_TRACER.instant("i")
    NULL_TRACER.counter("c", {"x": 1})
    NULL_TRACER.trip("anything")
    NULL_TRACER.add_consumer(lambda ev: None)


def test_null_tracer_region_is_one_shared_noop():
    calls = []
    clock = lambda: calls.append(1) or 0.0      # noqa: E731
    r1 = NULL_TRACER.region("repro.a", "t/x", rows=1)
    r2 = NULL_TRACER.region("repro.b", "t/y")
    assert r1 is r2
    with r1 as r:
        r.set(pages=3)
    # a disabled Tracer hands out the same no-op and reads no clock
    off = Tracer(clock=clock)
    off.enabled = False
    assert off.region("repro.c", "t/z") is r1
    with off.region("repro.c", "t/z"):
        pass
    assert calls == [] and off.events() == []


def test_region_records_a_span_on_the_tracer_clock():
    tr = Tracer(clock=fake_clock(10.0))
    with tr.region("repro.outer", "engine/e", rows=2) as outer:
        with tr.region("repro.inner", "engine/e"):
            pass
        outer.set(pages_written=5)
    with pytest.raises(KeyError):
        with tr.region("repro.failed", "engine/e"):
            raise KeyError("recorded all the same")
    inner, outer, failed = [ev for ev in tr.events() if ev[1] == "X"]
    # ticks of 1 ms: outer enters at 10.001, inner spans 10.002-10.003
    assert inner[2:6] == ("repro.inner", "engine/e", pytest.approx(10.002),
                          pytest.approx(0.001))
    assert outer[2:6] == ("repro.outer", "engine/e", pytest.approx(10.001),
                          pytest.approx(0.003))
    assert outer[6] == {"rows": 2, "pages_written": 5}
    assert inner[6] is None
    assert failed[2] == "repro.failed"


def _profiled_host_events(tmp_path, body):
    """Run ``body`` under a JAX profiler session and return the
    ``repro.`` host events of the trace it wrote."""
    import warnings
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    out = []
    with warnings.catch_warnings():
        # jaxlib's stats type predates Python 3.12's __module__ check
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(str(path)).planes:
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.duration_ns, dict(e.stats))
                        for e in line.events if e.name.startswith("repro.")]
    return sorted(out, key=lambda e: e[1])


def test_profiler_regions_land_in_the_xplane_with_their_stats(tmp_path):
    tr = Tracer()
    other = Tracer()

    def body():
        with tr.region("repro.decode.prepare", "engine/e", rows=3) as r:
            r.set(pages_reserved=7, pages_written=6)
            with tr.region("repro.decode.dispatch", "engine/e"):
                jax.jit(lambda x: x * 2)(np.ones(4)).block_until_ready()
        with other.region("repro.sched.emit", "sched/s"):
            pass
        with NULL_TRACER.region("repro.disabled", "engine/e"):
            pass

    # outside a profiler session the annotations record nothing
    with tr.region("repro.before_session", "engine/e"):
        pass
    events = _profiled_host_events(tmp_path, body)
    # every enabled tracer annotates; a disabled one does not
    assert [e[0] for e in events] == ["repro.decode.prepare",
                                      "repro.decode.dispatch",
                                      "repro.sched.emit"]
    (_, p0, pdur, stats), (_, d0, ddur, _), _emit = events
    assert stats == {"rows": 3, "pages_reserved": 7, "pages_written": 6}
    assert p0 <= d0 and d0 + ddur <= p0 + pdur
    # the ring holds the same spans, on the tracer's clock
    spans = [ev[2] for ev in tr.events() if ev[1] == "X"]
    assert spans == ["repro.before_session", "repro.decode.dispatch",
                     "repro.decode.prepare"]
    assert [ev[2] for ev in other.events()
            if ev[1] == "X"] == ["repro.sched.emit"]


def test_backend_compiles_are_counted_into_every_live_tracer():
    a, b = Tracer(), Tracer()
    before = a.stats()["compiles"]
    jax.jit(lambda x: x * 3 + 1)(np.arange(5.0)).block_until_ready()
    for tr in (a, b):
        st = tr.stats()
        assert st["compiles"] >= 1 and st["compile_s"] > 0.0
        compiles = [ev for ev in tr.events() if ev[2] == "repro.compile"]
        assert len(compiles) == st["compiles"]
        assert all(ev[6]["seconds"] > 0.0 for ev in compiles)
    assert a.stats()["compiles"] > before


def test_compile_counts_survive_concurrent_listeners():
    """Compiles are reported on whichever thread compiles: concurrent
    reports must not lose a count."""
    import sys
    import threading
    tr = Tracer(capacity=1 << 14)
    threads, per = 8, 500
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        workers = [threading.Thread(
            target=lambda: [tr._on_compile(0.5) for _ in range(per)])
            for _ in range(threads)]
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=30)
        assert not any(w.is_alive() for w in workers)
    finally:
        sys.setswitchinterval(old)
    st = tr.stats()
    assert st["compiles"] == threads * per
    assert st["compile_s"] == threads * per * 0.5


def test_tracers_built_while_compiles_are_reported():
    """A compile reported on one thread while another thread builds a
    tracer: the listener must not iterate the growing set of tracers
    (that raises inside the jit call that reported the compile)."""
    import sys
    import threading
    from repro.serving.observability import tracer as tracer_mod
    keep = [Tracer(capacity=4) for _ in range(2000)]
    stop, errors = threading.Event(), []

    def report():
        while not stop.is_set():
            try:
                tracer_mod._on_duration(tracer_mod.COMPILE_EVENT, 0.0)
            except Exception as exc:     # noqa: BLE001 - the failure
                errors.append(exc)
                return

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    reporter = threading.Thread(target=report)
    reporter.start()
    try:
        for _ in range(5000):
            keep.append(Tracer(capacity=4))
    finally:
        stop.set()
        reporter.join(timeout=30)
        sys.setswitchinterval(old)
    assert not reporter.is_alive()
    assert errors == []


def test_chrome_export_is_schema_valid(tmp_path):
    tr = Tracer(clock=fake_clock())
    tr.span("PREFILL", request_track(3), 1.0, 1.5, {"model": 0})
    tr.span("decode_step", backend_track("m0", "decode"), 1.5, 1.6)
    tr.instant("degrade", args={"rid": 3})
    tr.counter("m0:pool", {"pages_in_use": 7, "num_free": 9})
    path = tmp_path / "trace.json"
    payload = tr.export(str(path))
    assert validate_chrome_trace(payload) == []
    # the file round-trips to the same valid object
    assert validate_chrome_trace(json.loads(path.read_text())) == []
    # track mapping: one pid per group with metadata, µs timestamps
    names = {ev["name"] for ev in payload["traceEvents"]}
    assert {"process_name", "thread_name", "PREFILL", "degrade"} <= names
    span = next(ev for ev in payload["traceEvents"]
                if ev["name"] == "PREFILL")
    assert span["ts"] == pytest.approx(1.0e6) and \
        span["dur"] == pytest.approx(0.5e6)


def test_validator_rejects_malformed_payloads():
    assert validate_chrome_trace([]) != []
    assert validate_chrome_trace({}) != []
    bad_span = {"traceEvents": [{"ph": "X", "name": "s", "pid": 1,
                                 "tid": 1, "ts": 0.0}]}   # missing dur
    assert any("dur" in p for p in validate_chrome_trace(bad_span))
    bad_phase = {"traceEvents": [{"ph": "Q", "name": "s", "pid": 1,
                                  "tid": 1, "ts": 0.0}]}
    assert any("phase" in p for p in validate_chrome_trace(bad_phase))


# ===========================================================================
# Traced serving runs: closed span chains, transfer spans, token parity
# ===========================================================================

PROMPT_LENS = (12, 20, 9, 6)
MAX_NEW = 6


def serve(model, kind, tracer=None):
    backend = make_backend(model, kind)
    sched = PagedLLMScheduler(
        backends=[backend],
        cfg=PagedLLMConfig(max_new_tokens=MAX_NEW, prefill_chunk_pages=2),
        tracer=tracer)
    sched.warmup(sorted(set(PROMPT_LENS)))
    prompts = [prompt_of(n, i) for i, n in enumerate(PROMPT_LENS)]

    async def go():
        async with sched:
            handles = [sched.submit(p) for p in prompts]
            outs = await asyncio.gather(*handles)
            return handles, outs

    handles, outs = asyncio.run(go())
    return sched, handles, [np.asarray(o) for o in outs]


@pytest.mark.parametrize("kind", ["inproc", "disagg"])
def test_untraced_run_builds_no_span_arguments(model, kind, monkeypatch):
    """With tracing off the served path only enters the shared no-op
    region: it computes no counters for it and names no executor
    track, so tracing off costs one method call per region."""
    from repro.serving import backend as backend_mod
    from repro.serving.observability import tracer as tracer_mod
    counters, tracks = [], []
    monkeypatch.setattr(tracer_mod._NullRegion, "set",
                        lambda self, **args: counters.append(args))
    real_track = backend_mod.backend_track
    monkeypatch.setattr(backend_mod, "backend_track",
                        lambda *a: tracks.append(a) or real_track(*a))
    _, _, outs = serve(model, kind)
    assert len(outs) == len(PROMPT_LENS)
    assert counters == []
    # binding the (null) tracer names the engine and pool tracks once;
    # the executor calls name none
    assert [a for a in tracks
            if not a[1].endswith(("engine", "pool"))] == []


def chain_of(events, rid):
    track = request_track(rid)
    return [(ph, name, ts, dur)
            for _, ph, name, track_, ts, dur, _ in events if track_ == track]


@pytest.mark.parametrize("kind", ["inproc", "disagg"])
def test_traced_run_chains_close_and_tokens_match_untraced(model, kind,
                                                           tmp_path):
    _, _, baseline = serve(model, kind)           # untraced reference
    tracer = Tracer()
    sched, handles, outs = serve(model, kind, tracer=tracer)

    # tracing must be invisible to the tokens
    for ref, out in zip(baseline, outs):
        np.testing.assert_array_equal(ref, out)

    payload = tracer.export(str(tmp_path / f"{kind}.json"))
    assert validate_chrome_trace(payload) == []

    events = tracer.events()
    names = {ev[2] for ev in events}
    # the served path's regions: the executor call, the engine's four
    # phases of a decode step and of a prefill chunk, the scheduler's
    # host work; the scheduler no longer times the decode call itself
    assert {"repro.executor.run", "repro.sched.admit",
            "repro.sched.emit"} <= names
    for phase in ("prepare", "dispatch", "wait", "commit"):
        assert f"repro.decode.{phase}" in names
        assert f"repro.prefill.{phase}" in names
    assert "DECODE_STEP" not in names
    # one decode prepare inside each executor decode call, carrying the
    # step's rows and pages (written never exceeds reserved)
    spans = [ev for ev in events if ev[1] == "X"]
    runs = [ev for ev in spans if ev[2] == "repro.executor.run"
            and ev[6]["op"] == "decode_step"]
    assert len(runs) == sched.decode_batches > 0
    prepares = [ev for ev in spans if ev[2] == "repro.decode.prepare"]
    for run in runs:
        inside = [ev for ev in prepares
                  if run[4] <= ev[4] and ev[4] + ev[5] <= run[4] + run[5]]
        assert len(inside) == 1
        args = inside[0][6]
        assert 1 <= args["rows"] <= 4
        assert 0 < args["pages_written"] <= args["pages_reserved"]
    assert all(ev[6]["queued_us"] >= 0 for ev in runs)
    chunks = [ev[6] for ev in spans if ev[2] == "repro.prefill.prepare"]
    assert all(0 < a["tokens"] <= a["prompt_len"] for a in chunks)
    assert any(n.startswith("PREFILL_CHUNK[") for n in names)
    # KV_TRANSFER spans appear exactly on the disaggregated backend
    assert ("KV_TRANSFER" in names) == (kind == "disagg")

    # closed ADMIT -> QUEUED -> PREFILL -> DECODE -> FINISH chain per
    # completed request, with exactly-chained endpoints
    for h in handles:
        req = h.request
        chain = {name: (ph, ts, dur)
                 for ph, name, ts, dur in chain_of(events, req.rid)}
        for name in ("ADMIT", "QUEUED", "PREFILL", "DECODE", "FINISH"):
            assert name in chain, (req.rid, sorted(chain))
        assert chain["ADMIT"][1] == req.admitted_t
        assert chain["QUEUED"][1] == req.admitted_t
        assert chain["QUEUED"][1] + chain["QUEUED"][2] == pytest.approx(
            req.started_t, abs=1e-6)
        assert chain["PREFILL"][1] == req.started_t
        assert chain["DECODE"][1] == req.first_token_t
        assert chain["DECODE"][1] + chain["DECODE"][2] == pytest.approx(
            req.finished_t, abs=1e-6)
        assert chain["FINISH"][1] == req.finished_t
        chunks = [n for _, n, _, _ in chain_of(events, req.rid)
                  if n.startswith("PREFILL_CHUNK[")]
        assert chunks == [f"PREFILL_CHUNK[{i}]"
                          for i in range(len(chunks))] and chunks

    # the flattened dashboard keys ride on the paged snapshot
    snap = sched.snapshot()
    assert snap["pool_pages_in_use"] == 0
    assert snap["prewarm_residents"] >= 0
    assert snap["inflight_chunks"] == 0
    assert 0.0 <= snap["logit_cache_hit_rate"] <= 1.0
    assert snap["trace"]["recorded"] > 0
    # the gauge loop (or the final stop() sample) recorded counters
    assert any(ev[1] == "C" for ev in events)
    if kind == "disagg":
        assert snap["phase_transfer_p99_ms"] > 0.0


# ===========================================================================
# Gauges
# ===========================================================================

def test_sample_gauges_records_pool_cache_and_load_series(model):
    backend = make_backend(model, "disagg")
    sched = PagedLLMScheduler(backends=[backend])
    tracer = Tracer(clock=fake_clock())
    sample_gauges(tracer, sched)
    counters = {ev[2]: ev[6] for ev in tracer.events() if ev[1] == "C"}
    name = backend.name
    assert f"{name}:pool" in counters
    assert f"{name}:prefill_pool" in counters      # disagg staging pool
    assert {"pages_in_use", "num_free",
            "cow_headroom"} <= set(counters[f"{name}:pool"])
    assert counters[f"{name}:load"]["queued"] == 0
    assert counters[f"{name}:load"]["inflight_chunks"] == 0
    assert "decoding" in counters[f"{name}:load"]
    assert f"{name}:logit_cache" in counters
    assert counters[f"{name}:prewarm"]["residents"] >= 0


def test_sample_gauges_disabled_is_noop(model):
    backend = make_backend(model, "inproc")
    sched = PagedLLMScheduler(backends=[backend])
    sample_gauges(NULL_TRACER, sched)              # must not raise


# ===========================================================================
# Flight recorder + metrics tracing bridge
# ===========================================================================

def _req(rid=1, admitted=1.0, started=1.5, first=2.5, finished=3.0,
         transfer=0.0, model_id=0, deadline=100.0):
    req = Request(rid=rid, x=np.zeros(4, np.int32), arrival_t=admitted,
                  deadline_t=deadline, params=SamplingParams())
    req.model_id = model_id
    req.admitted_t = admitted
    req.started_t = started
    req.first_token_t = first
    req.transfer_wait_s = transfer
    req.finished_t = finished      # terminal helpers below overwrite this
    return req


def test_flight_recorder_trips_on_failure_and_rate_limits(tmp_path):
    path = tmp_path / "flight.json"
    tracer = Tracer(clock=fake_clock(), flight_recorder_path=str(path),
                    flight_recorder_min_interval_s=1e9)
    metrics = SchedulerMetrics([1.0])
    metrics.bind_tracer(tracer)
    req = _req()
    req.fail(RuntimeError("boom"), 3.0)
    metrics.on_fail(req)
    assert tracer.trips == 1 and tracer.dumps == 1
    payload = json.loads(path.read_text())
    assert validate_chrome_trace(payload) == []
    assert payload["otherData"]["reason"] == "request_failed"
    # a failure storm inside the min interval counts but doesn't re-dump
    req2 = _req(rid=2)
    req2.fail(RuntimeError("boom"), 3.5)
    metrics.on_fail(req2)
    assert tracer.trips == 2 and tracer.dumps == 1


def test_flight_recorder_manual_dump_windows_events(tmp_path):
    tracer = Tracer(clock=lambda: 100.0)
    tracer.instant("old", t=10.0)
    tracer.instant("recent", t=95.0)
    path = tracer.flight_recorder_dump(str(tmp_path / "dump.json"),
                                       window_s=20.0)
    payload = json.loads((tmp_path / "dump.json").read_text())
    assert path == str(tmp_path / "dump.json")
    names = {ev["name"] for ev in payload["traceEvents"]
             if ev["ph"] == "i"}
    assert names == {"recent"}


def test_slo_violation_trips_and_instants_flow_to_snapshot():
    tracer = Tracer(clock=fake_clock())
    metrics = SchedulerMetrics([1.0, 2.0])
    metrics.bind_tracer(tracer)
    late = _req(deadline=2.0)                      # finished_t=3.0 > deadline
    late.complete(np.zeros(4), 3.0)
    metrics.on_complete(late)
    assert tracer.trips == 1                       # no path: count only
    metrics.on_degrade(_req(rid=2), 1, 0)
    metrics.on_shed(_req(rid=3))
    snap = metrics.snapshot(now=4.0)
    assert snap["trace_instants"]["degrade"] == 1
    assert snap["trace_instants"]["shed"] == 1
    # the request chain itself also flowed through the consumer
    assert snap["trace_instants"]["ADMIT"] == 1
    assert snap["trace"]["recorded"] > 0


# ===========================================================================
# Metrics satellites: rejected queue, attribution, seeds, lifecycle
# ===========================================================================

def test_rejected_queue_wait_is_kept_out_of_served_percentiles():
    metrics = SchedulerMetrics([1.0])
    cancelled = _req()                  # admitted 1.0, started 1.5
    cancelled.cancel(2.0)
    metrics.on_cancel(cancelled)
    failed = _req(rid=2, admitted=1.0, started=0.0, first=0.0)
    failed.fail(RuntimeError("x"), 1.4)     # failed while still queued
    metrics.on_fail(failed)
    snap = metrics.snapshot(now=3.0)
    assert snap["rejected_count"] == 2
    assert snap["rejected_queue_p50_ms"] > 0.0
    assert len(metrics.queue_lat) == 0      # served percentiles untouched
    # a hard shed never queued (admitted_t == 0): counted by on_shed's
    # budget_exceeded, not as a rejected queue wait
    shed = _req(rid=3, admitted=0.0, started=0.0, first=0.0)
    shed.fail(RuntimeError("shed"), 1.0)
    metrics.on_shed(shed)
    metrics.on_fail(shed)
    assert metrics.snapshot(now=3.0)["rejected_count"] == 2


def test_phase_attribution_decomposes_end_to_end_latency():
    metrics = SchedulerMetrics([1.0])
    req = _req(admitted=1.0, started=1.5, first=2.5, finished=3.0,
               transfer=0.25)
    req.complete(np.zeros(4), 3.0)
    metrics.on_complete(req)
    snap = metrics.snapshot(now=4.0)
    assert snap["phase_queue_p50_ms"] == pytest.approx(500.0)
    assert snap["phase_prefill_p50_ms"] == pytest.approx(750.0)
    assert snap["phase_transfer_p50_ms"] == pytest.approx(250.0)
    assert snap["phase_decode_p50_ms"] == pytest.approx(500.0)
    # phases tile admission -> finish exactly
    total = sum(snap[f"phase_{p}_p50_ms"]
                for p in ("queue", "prefill", "transfer", "decode"))
    assert total == pytest.approx((req.finished_t - req.admitted_t) * 1e3)
    assert snap["ttft_p50_ms_by_model"][0] == pytest.approx(1500.0)


def test_per_model_itl_reservoirs():
    metrics = SchedulerMetrics([1.0, 2.0])
    metrics.on_decode_gap(1, 0.010)
    snap = metrics.snapshot(now=1.0)
    assert snap["itl_p50_ms"] == pytest.approx(10.0)
    assert snap["itl_p50_ms_by_model"] == [0.0, pytest.approx(10.0)]


def test_reservoirs_get_distinct_seeds():
    metrics = SchedulerMetrics([1.0, 2.0])
    reservoirs = [metrics.queue_lat, metrics.service_lat, metrics.total_lat,
                  metrics.ttft_lat, metrics.itl_lat,
                  metrics.rejected_queue_lat,
                  *metrics.phase_lat.values(), *metrics.ttft_by_model,
                  *metrics.itl_by_model, *metrics.backend_queue_wait,
                  *metrics.transfer_lat]
    states = [r._rng.getstate() for r in reservoirs]
    assert len({str(s) for s in states}) == len(states), \
        "same-seeded reservoirs evict correlated slots"


def test_snapshot_elapsed_mid_run_and_across_restarts():
    metrics = SchedulerMetrics([1.0], clock=lambda: 1e9)
    metrics.on_start(100.0)
    mid = metrics.snapshot(now=105.0)
    assert mid["elapsed_s"] == pytest.approx(5.0)    # live: runs to now
    metrics.on_stop(110.0)
    assert metrics.snapshot(now=999.0)["elapsed_s"] == pytest.approx(10.0)
    metrics.on_start(200.0)                          # restart accumulates
    assert metrics.snapshot(now=207.0)["elapsed_s"] == pytest.approx(17.0)
    req = _req()
    req.complete(np.zeros(4), 3.0)
    metrics.on_complete(req)
    snap = metrics.snapshot(now=205.0)
    assert snap["throughput_rps"] == pytest.approx(1.0 / 15.0)
