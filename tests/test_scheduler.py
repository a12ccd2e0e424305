"""Unit + integration tests for the continuous-batching mux scheduler
(repro.serving.scheduler)."""
import asyncio

import numpy as np
import pytest

from repro.serving.scheduler import (BatchingPolicy, MicroBatcher,
                                     ModelQueue, MuxScheduler,
                                     SchedulerConfig, SchedulerMetrics,
                                     TrafficConfig, arrival_times, replay)
from repro.serving.scheduler.request import Request, RequestState


def _req(rid, deadline_t, x=None):
    return Request(rid=rid, x=x if x is not None else np.zeros(2),
                   arrival_t=0.0, deadline_t=deadline_t)


# ---------------------------------------------------------------------------
# ModelQueue + MicroBatcher
# ---------------------------------------------------------------------------

def test_queue_pops_in_deadline_order():
    q = ModelQueue(0)
    for rid, dl in [(0, 5.0), (1, 1.0), (2, 3.0)]:
        q.push(_req(rid, dl), now=0.0)
    batcher = MicroBatcher(BatchingPolicy(max_batch_size=8))
    batch = batcher.form(q, now=0.0)
    assert [r.rid for r in batch] == [1, 2, 0]
    assert all(r.state is RequestState.BATCHED for r in batch)


def test_deadline_tie_breaks_fifo():
    q = ModelQueue(0)
    for rid in range(4):
        q.push(_req(rid, deadline_t=1.0), now=0.0)
    batch = MicroBatcher(BatchingPolicy(max_batch_size=8)).form(q, now=0.0)
    assert [r.rid for r in batch] == [0, 1, 2, 3]


def test_batch_full_triggers_ready():
    q = ModelQueue(0)
    batcher = MicroBatcher(BatchingPolicy(max_batch_size=3, max_wait_ms=1e9))
    for rid in range(2):
        q.push(_req(rid, 1.0), now=0.0)
    assert not batcher.ready(q, now=0.0)
    q.push(_req(2, 1.0), now=0.0)
    assert batcher.ready(q, now=0.0)


def test_max_wait_flushes_partial_batch():
    q = ModelQueue(0)
    batcher = MicroBatcher(BatchingPolicy(max_batch_size=8, max_wait_ms=5.0))
    q.push(_req(0, 1.0), now=10.0)
    assert not batcher.ready(q, now=10.001)          # 1ms old: wait
    assert batcher.ready(q, now=10.006)              # 6ms old: flush
    assert batcher.time_until_ready(q, now=10.001) == pytest.approx(0.004)
    assert batcher.time_until_ready(q, now=10.2) == 0.0
    assert batcher.time_until_ready(ModelQueue(1), now=0.0) is None


def test_form_respects_max_batch_size_and_leaves_rest():
    q = ModelQueue(0)
    for rid in range(5):
        q.push(_req(rid, deadline_t=float(rid)), now=0.0)
    batcher = MicroBatcher(BatchingPolicy(max_batch_size=3))
    batch = batcher.form(q, now=0.0)
    assert [r.rid for r in batch] == [0, 1, 2]
    assert len(q) == 2


def test_form_bucket_rows_follow_batch_order():
    batcher = MicroBatcher(BatchingPolicy(max_batch_size=4))
    batch = [_req(i, 1.0, x=np.full(3, float(i + 1))) for i in range(2)]
    bucket, valid = batcher.form_bucket(batch)
    assert bucket.shape == (4, 3)
    np.testing.assert_array_equal(bucket[0], np.full(3, 1.0))
    np.testing.assert_array_equal(bucket[1], np.full(3, 2.0))
    np.testing.assert_array_equal(bucket[2:], np.zeros((2, 3)))
    np.testing.assert_array_equal(valid, [True, True, False, False])


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------

def test_metrics_counters_and_eq14():
    m = SchedulerMetrics(costs=[1.0, 4.0])
    m.on_start(0.0)
    reqs = []
    for rid, model, t_done in [(0, 0, 0.010), (1, 0, 0.020), (2, 1, 0.030)]:
        r = _req(rid, deadline_t=1.0)
        r.model_id = model
        r.flops = [1.0, 4.0][model]
        r.admitted_t, r.started_t, r.finished_t = 0.0, t_done / 2, t_done
        reqs.append(r)
        m.on_arrival(r)
        m.on_admit(r)
        m.on_complete(r)
    m.on_batch(0, 2, 4)
    m.on_batch(1, 1, 4)
    m.on_model_busy(0, 0.5)
    m.on_stop(2.0)
    snap = m.snapshot()
    assert snap["arrived"] == snap["admitted"] == snap["completed"] == 3
    assert snap["slo_violations"] == 0
    assert snap["throughput_rps"] == pytest.approx(1.5)
    assert snap["called_fraction"] == [pytest.approx(2 / 3),
                                       pytest.approx(1 / 3)]
    assert snap["utilization"][0] == pytest.approx(0.25)
    assert snap["mean_batch_fill"] == pytest.approx(3 / 8)
    # Eq. 14: mean flops (1+1+4)/3 = 2 vs always-largest 4
    assert snap["mean_flops"] == pytest.approx(2.0)
    assert snap["flops_saved_frac"] == pytest.approx(0.5)
    assert snap["flops_saving_factor"] == pytest.approx(2.0)
    assert snap["total_p50_ms"] == pytest.approx(20.0)


def test_metrics_elapsed_accumulates_across_runs():
    m = SchedulerMetrics(costs=[1.0])
    m.on_start(0.0)
    m.on_stop(2.0)
    m.on_start(10.0)                       # restart
    snap = m.snapshot(now=11.0)            # mid second run
    # cumulative counters divide by cumulative serving time (2s + 1s),
    # not just the latest run's elapsed
    assert snap["elapsed_s"] == pytest.approx(3.0)
    m.on_stop(12.0)
    assert m.snapshot()["elapsed_s"] == pytest.approx(4.0)


def test_metrics_slo_violation_counted():
    m = SchedulerMetrics(costs=[1.0])
    r = _req(0, deadline_t=0.005)
    r.model_id, r.flops = 0, 1.0
    r.admitted_t, r.started_t, r.finished_t = 0.0, 0.001, 0.010
    m.on_complete(r)
    assert m.slo_violations == 1


# ---------------------------------------------------------------------------
# Traffic generation
# ---------------------------------------------------------------------------

def test_arrival_times_deterministic_and_rate():
    tc = TrafficConfig(rate=1000.0, num_requests=500, seed=3)
    t1, t2 = arrival_times(tc), arrival_times(tc)
    np.testing.assert_array_equal(t1, t2)
    assert np.all(np.diff(t1) >= 0)
    # mean rate within 20% of nominal for 500 samples
    assert t1[-1] == pytest.approx(0.5, rel=0.2)


def test_bursty_mean_rate_matches_nominal():
    tc = TrafficConfig(rate=1000.0, num_requests=20_000, pattern="bursty",
                       burst_factor=4.0, seed=1)
    t = arrival_times(tc)
    realized = len(t) / t[-1]
    assert realized == pytest.approx(1000.0, rel=0.15)


def test_latency_reservoir_is_bounded():
    from repro.serving.scheduler import LatencyReservoir
    r = LatencyReservoir(max_samples=64)
    for i in range(10_000):
        r.add(i / 1000.0)
    assert len(r) == 10_000              # observations counted
    assert len(r._samples) == 64         # memory bounded
    # a uniform sample of 0..10s should have a mid-range median
    assert 1_000.0 < r.percentile_ms(50) < 9_000.0


def test_bursty_arrivals_are_burstier_than_poisson():
    n = 2000
    pois = arrival_times(TrafficConfig(rate=1000.0, num_requests=n, seed=0))
    burst = arrival_times(TrafficConfig(rate=1000.0, num_requests=n,
                                        pattern="bursty", burst_factor=8.0,
                                        seed=0))
    cv = lambda t: np.std(np.diff(t)) / np.mean(np.diff(t))
    assert cv(burst) > cv(pois)          # CV of exp(λ) is 1; MMPP > 1
    with pytest.raises(ValueError):
        arrival_times(TrafficConfig(rate=1.0, num_requests=1,
                                    pattern="sawtooth"))


# ---------------------------------------------------------------------------
# End-to-end runtime (duck-typed server, no training needed)
# ---------------------------------------------------------------------------

class FakeServer:
    """Routes by the first feature's magnitude; model m scales by m+1."""

    def __init__(self, n=3):
        self.costs = np.asarray([1.0, 2.0, 4.0][:n], np.float32)
        self._n = n

    @property
    def num_models(self):
        return self._n

    def probe_weights(self, x):
        level = np.clip(np.abs(np.asarray(x)[:, 0]).astype(int), 0,
                        self._n - 1)
        w = np.zeros((len(level), self._n), np.float32)
        w[np.arange(len(level)), level] = 1.0
        return w

    def select(self, w):
        return np.argmax(np.asarray(w), axis=-1).astype(np.int32)

    def model_step(self, m, bucket):
        return np.asarray(bucket) * float(m + 1)


def test_scheduler_end_to_end_outputs_and_metrics():
    server = FakeServer()
    xs = [np.full(4, float(i % 3), np.float32)
          for i in range(24)]                           # routes 0,1,2,0,...

    async def main():
        sched = MuxScheduler(server, SchedulerConfig(max_batch_size=4,
                                                     max_wait_ms=2.0))
        async with sched:
            handles = [sched.submit(x) for x in xs]   # awaitable handles
            return sched, await asyncio.gather(*handles)

    sched, outs = asyncio.run(main())
    for i, (x, out) in enumerate(zip(xs, outs)):
        m = i % 3
        np.testing.assert_array_equal(out, x * (m + 1))
        np.testing.assert_array_equal(out, sched.reference_output(x, m))
    snap = sched.metrics.snapshot()
    assert snap["completed"] == 24
    assert snap["failed"] == 0
    assert snap["called_fraction"] == [pytest.approx(1 / 3)] * 3
    assert snap["mean_flops"] == pytest.approx((1 + 2 + 4) / 3)
    assert snap["batches"] >= 6          # >= ceil(8/4) buckets per model
    assert len(sched.queues[0]) == 0     # drained on stop


def test_submit_many_admits_batch_with_one_probe():
    class CountingServer(FakeServer):
        probe_calls = 0

        def probe_weights(self, x):
            CountingServer.probe_calls += 1
            return super().probe_weights(x)

    server = CountingServer()
    xs = [np.full(4, float(i % 3), np.float32) for i in range(6)]

    async def main():
        sched = MuxScheduler(server, SchedulerConfig(max_batch_size=4,
                                                     max_wait_ms=1.0,
                                                     probe_batch_size=8))
        async with sched:
            futures = sched.submit_many(xs)
            return await asyncio.gather(*futures)

    outs = asyncio.run(main())
    assert CountingServer.probe_calls == 1
    for i, out in enumerate(outs):
        np.testing.assert_array_equal(out, xs[i] * (i % 3 + 1))


def test_live_snapshot_reports_nonzero_rates():
    server = FakeServer()

    async def main():
        sched = MuxScheduler(server, SchedulerConfig(max_batch_size=2,
                                                     max_wait_ms=1.0))
        async with sched:
            await sched.submit(np.zeros(4, np.float32))
            return sched.metrics.snapshot()     # mid-run: before stop()

    snap = asyncio.run(main())
    assert snap["completed"] == 1
    assert snap["elapsed_s"] > 0.0
    assert snap["throughput_rps"] > 0.0


def test_restarted_scheduler_snapshot_not_negative():
    server = FakeServer()

    async def main():
        sched = MuxScheduler(server, SchedulerConfig(max_batch_size=2,
                                                     max_wait_ms=1.0))
        async with sched:
            await sched.submit(np.zeros(4, np.float32))
        async with sched:                       # restart the same instance
            await sched.submit(np.zeros(4, np.float32))
            snap = sched.metrics.snapshot()     # mid-run after restart
        return snap

    snap = asyncio.run(main())
    # a stale stopped_t from the first run would drive elapsed negative
    assert snap["elapsed_s"] > 0.0
    assert snap["throughput_rps"] >= 0.0
    assert all(u >= 0.0 for u in snap["utilization"])


def test_admission_probe_shape_is_fixed_across_burst_sizes():
    class ShapeRecordingServer(FakeServer):
        shapes = []

        def probe_weights(self, x):
            ShapeRecordingServer.shapes.append(np.asarray(x).shape)
            return super().probe_weights(x)

    server = ShapeRecordingServer()

    async def main():
        sched = MuxScheduler(server, SchedulerConfig(max_batch_size=4,
                                                     max_wait_ms=1.0,
                                                     probe_batch_size=4))
        async with sched:
            futs = []
            for burst in (1, 2, 3, 5):   # 5 > probe batch: chunked
                futs += sched.submit_many(
                    [np.zeros(4, np.float32)] * burst)
            await asyncio.gather(*futs)

    asyncio.run(main())
    # every probe call padded to the fixed (probe_batch, ...) shape —
    # a novel shape would mean an XLA recompile on the event loop
    assert set(ShapeRecordingServer.shapes) == {(4, 4)}


def test_signature_mismatch_rejected_at_admission_not_batch():
    server = FakeServer()

    async def main():
        sched = MuxScheduler(server, SchedulerConfig(max_batch_size=8,
                                                     max_wait_ms=1.0))
        async with sched:
            # the first successful admission sets the serving signature
            good_a = sched.submit(np.zeros(4, np.float32))
            # a mismatched request fails ITS OWN future at admission —
            # it must not reach the queue and poison good_a's bucket
            bad = sched.submit(np.zeros(7, np.float32))
            with pytest.raises(ValueError, match="serving signature"):
                await bad
            np.testing.assert_array_equal(await good_a, np.zeros(4))
            x = np.array([0.0, 5.0, 6.0, 7.0], np.float32)
            out = await sched.submit(x)
            np.testing.assert_array_equal(out, x)   # model 0 scales by 1
        snap = sched.metrics.snapshot()
        assert snap["completed"] == 2 and snap["failed"] == 1

    asyncio.run(main())


def test_admission_failure_resolves_futures_and_keeps_books_closed():
    class PickyServer(FakeServer):
        def probe_weights(self, x):
            if np.asarray(x).shape[-1] != 4:
                raise ValueError("bad feature width")
            return super().probe_weights(x)

    server = PickyServer()

    async def main():
        sched = MuxScheduler(server, SchedulerConfig(max_batch_size=2,
                                                     max_wait_ms=1.0))
        async with sched:
            bad = sched.submit(np.zeros(9, np.float32))
            with pytest.raises(ValueError, match="bad feature width"):
                await bad
            out = await sched.submit(np.zeros(4, np.float32))
            np.testing.assert_array_equal(out, np.zeros(4))
        snap = sched.metrics.snapshot()
        # books closed: every arrival is either completed or failed
        assert snap["arrived"] == snap["completed"] + snap["failed"] == 2
        assert snap["failed"] == 1

    asyncio.run(main())


def test_scheduler_worker_failure_propagates():
    class BrokenServer(FakeServer):
        def model_step(self, m, bucket):
            raise RuntimeError("bucket exploded")

    async def main():
        sched = MuxScheduler(BrokenServer(),
                             SchedulerConfig(max_batch_size=2,
                                             max_wait_ms=1.0))
        async with sched:
            handle = sched.submit(np.zeros(4))
            with pytest.raises(RuntimeError, match="bucket exploded"):
                await handle
        assert sched.metrics.failed == 1

    asyncio.run(main())


def test_scheduler_stop_drains_partial_batches():
    server = FakeServer()

    async def main():
        # max_wait so long the only way out is the stop()-flush.
        # submit_nowait is the one-shot compat shim (handle.future) —
        # this test doubles as its pin.
        sched = MuxScheduler(server, SchedulerConfig(max_batch_size=64,
                                                     max_wait_ms=60_000.0))
        await sched.start()
        futures = [sched.submit_nowait(np.full(4, 1.0)) for _ in range(3)]
        await sched.stop(drain=True)
        outs = [f.result() for f in futures]
        for out in outs:
            np.testing.assert_array_equal(out, np.full(4, 2.0))
        assert sched.metrics.completed == 3
        with pytest.raises(RuntimeError, match="not running"):
            sched.submit_nowait(np.zeros(4))

    asyncio.run(main())


def test_lifecycle_double_start_raises_and_stop_is_idempotent():
    """SchedulerLifecycle contract (shared by MuxScheduler and
    PagedLLMScheduler): start() twice raises, stop() twice is a no-op,
    and a stopped scheduler rejects submissions."""
    server = FakeServer()

    async def main():
        sched = MuxScheduler(server, SchedulerConfig(max_batch_size=2,
                                                     max_wait_ms=1.0))
        await sched.start()
        with pytest.raises(RuntimeError, match="already started"):
            await sched.start()
        await sched.submit(np.zeros(4, np.float32))
        await sched.stop()
        await sched.stop()                      # idempotent
        with pytest.raises(RuntimeError, match="not running"):
            sched.submit_nowait(np.zeros(4, np.float32))
        assert sched.metrics.completed == 1

    asyncio.run(main())


def test_lifecycle_drain_waits_for_all_inflight():
    server = FakeServer()

    async def main():
        sched = MuxScheduler(server, SchedulerConfig(max_batch_size=4,
                                                     max_wait_ms=1.0))
        async with sched:
            handles = [sched.submit(np.zeros(4, np.float32))
                       for _ in range(6)]
            await sched.drain()
            assert all(h.done() for h in handles)
        assert sched.metrics.completed == 6

    asyncio.run(main())


def test_lifecycle_cancel_without_drain_fails_pending_futures():
    """A no-drain stop must not leave futures unresolved: queued work
    is cancelled with the workers."""
    class SlowServer(FakeServer):
        def model_step(self, m, bucket):
            import time as _t
            _t.sleep(0.05)
            return super().model_step(m, bucket)

    async def main():
        sched = MuxScheduler(SlowServer(),
                             SchedulerConfig(max_batch_size=64,
                                             max_wait_ms=60_000.0))
        await sched.start()
        handles = [sched.submit(np.zeros(4, np.float32))
                   for _ in range(3)]
        await sched.stop(drain=False)
        assert all(h.done() for h in handles)    # resolved or cancelled

    asyncio.run(main())


def test_open_loop_replay_respects_schedule():
    server = FakeServer()
    xs = [np.zeros(4) for _ in range(10)]

    async def main():
        sched = MuxScheduler(server, SchedulerConfig(max_batch_size=4,
                                                     max_wait_ms=1.0))
        async with sched:
            times = arrival_times(TrafficConfig(rate=500.0, num_requests=10,
                                                seed=0))
            futures = await replay(sched.submit, xs, times)
            await asyncio.gather(*futures)
        return sched.metrics.snapshot()

    snap = asyncio.run(main())
    assert snap["completed"] == 10
    assert snap["slo_violations"] == 0   # 100ms default SLO, light load


@pytest.mark.parametrize("pattern,rate", [("poisson", 400.0),
                                          ("bursty", 200.0)])
def test_real_mux_server_outputs_bitwise_match_direct_call(pattern, rate):
    """A random-init three-model CNN zoo behind a real MuxServer, fed
    an open-loop schedule: every output is bitwise the selected model
    called directly on that request alone, at the scheduler's bucket
    shape (the only shape at which row results are stable)."""
    import jax

    from repro.core.multiplexer import init_image_backbone, init_mux
    from repro.models.cnn import ZOO_SPECS, cnn_forward, init_zoo, zoo_costs
    from repro.serving.mux_server import MuxServer, MuxServerConfig

    zoo_names, image_size = ("zoo_xxs", "zoo_xs", "zoo_s"), 16
    zoo = init_zoo(jax.random.key(0), num_classes=10, names=zoo_names)
    costs = zoo_costs(zoo_names, image_size=image_size)
    mux = init_mux(jax.random.key(1),
                   backbone=init_image_backbone(jax.random.key(2),
                                                meta_dim=32),
                   model_names=list(zoo_names), costs=costs, meta_dim=32,
                   proj_dim=16)

    def make_fn(n):
        cps = ZOO_SPECS[n].get("convs_per_stage", 1)
        return lambda xs: cnn_forward(zoo[n], xs, convs_per_stage=cps)[0]

    server = MuxServer(mux, [make_fn(n) for n in zoo_names],
                       [costs[n] for n in zoo_names], MuxServerConfig())
    traffic = TrafficConfig(rate=rate, num_requests=32, pattern=pattern,
                            seed=0)
    xs = np.asarray(jax.random.normal(
        jax.random.key(3), (traffic.num_requests, image_size, image_size,
                            3)))

    async def main():
        sched = MuxScheduler(server, SchedulerConfig(
            max_batch_size=8, max_wait_ms=4.0, default_slo_ms=250.0))
        sched.warmup(xs[0])
        async with sched:
            futures = await replay(sched.submit, list(xs),
                                   arrival_times(traffic))
            return sched, await asyncio.gather(*futures)

    sched, outs = asyncio.run(main())
    for i, out in enumerate(outs):
        m = sched.reference_assignment(xs[i])
        assert np.array_equal(np.asarray(out),
                              sched.reference_output(xs[i], m)), i
    snap = sched.metrics.snapshot()
    assert snap["completed"] == traffic.num_requests and snap["failed"] == 0
