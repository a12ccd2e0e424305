"""The streaming generation API: SamplingParams/GenerationHandle
surface, token events, chunked prefill (interleave + parity),
cancellation at every phase (queue-wait / mid-chunked-prefill /
mid-decode) with zero page leaks, idempotent terminal transitions,
the deadline-degrade admission hook, and the cross-request logit
cache."""
import asyncio

import jax
import numpy as np
import pytest

from repro.configs.base import LayerSpec, ModelConfig
from repro.models import transformer as tf
from repro.serving.engine import Engine, ServeConfig
from repro.serving.scheduler import (EventType, MuxScheduler, PagedLLMConfig,
                                     PagedLLMScheduler, Request, RequestState,
                                     SamplingParams, SchedulerConfig)
from repro.serving.scheduler.batcher import ModelQueue

PS = 4          # page size everywhere here


def tiny_config() -> ModelConfig:
    return ModelConfig(name="stream-tiny", arch_type="dense", num_layers=2,
                       d_model=32, d_ff=64, vocab_size=64, num_heads=4,
                       num_kv_heads=2, head_dim=8, compute_dtype="float32",
                       param_dtype="float32", kv_cache_dtype="float32")


@pytest.fixture(scope="module")
def model():
    cfg = tiny_config()
    return cfg, tf.init_params(cfg, jax.random.key(0))


def make_engine(model, num_pages=40, decode_batch=4, **kw) -> Engine:
    cfg, params = model
    eng = Engine(cfg, params, ServeConfig(max_len=64))
    eng.init_paged(num_pages=num_pages, page_size=PS,
                   decode_batch=decode_batch, **kw)
    return eng


def prompt_of(n, fold=0, model=None):
    cfg = model[0] if model else tiny_config()
    return np.asarray(jax.random.randint(jax.random.fold_in(
        jax.random.key(5), fold), (n,), 0, cfg.vocab_size))


# ---------------------------------------------------------------------------
# Idempotent terminal transitions (regression: cancel racing completion)
# ---------------------------------------------------------------------------

def test_terminal_transitions_first_one_wins():
    """complete/fail/cancel are idempotent: the first transition wins,
    every later call is a no-op returning False — a cancel racing a
    worker completion can no longer depend on worker timing."""
    req = Request(rid=0, x=np.zeros(2), arrival_t=0.0, deadline_t=1.0)
    assert req.complete("out", 0.5)
    assert req.state is RequestState.COMPLETED
    assert not req.fail(RuntimeError("late"), 0.6)      # loses the race
    assert not req.cancel(0.7)
    assert not req.complete("other", 0.8)
    assert req.state is RequestState.COMPLETED
    assert req.output == "out" and req.finished_t == 0.5

    req2 = Request(rid=1, x=np.zeros(2), arrival_t=0.0, deadline_t=1.0)
    assert req2.cancel(0.3)
    assert not req2.complete("out", 0.4)                # completion loses
    assert req2.state is RequestState.CANCELLED
    assert req2.finish_reason == "cancelled"

    req3 = Request(rid=2, x=np.zeros(2), arrival_t=0.0, deadline_t=1.0)
    assert req3.fail(ValueError("boom"), 0.2)
    assert not req3.fail(ValueError("again"), 0.3)      # counted once
    assert req3.finished_t == 0.2


def test_cancel_racing_completion_resolves_future_once():
    async def main():
        loop = asyncio.get_running_loop()
        req = Request(rid=0, x=np.zeros(2), arrival_t=0.0, deadline_t=1.0,
                      future=loop.create_future())
        assert req.complete("out", 0.5)
        assert not req.cancel(0.6)          # future already resolved
        assert await req.future == "out"    # not CancelledError

        req2 = Request(rid=1, x=np.zeros(2), arrival_t=0.0, deadline_t=1.0,
                       future=loop.create_future())
        assert req2.cancel(0.5)
        assert not req2.complete("out", 0.6)
        with pytest.raises(asyncio.CancelledError):
            await req2.future

    asyncio.run(main())


def test_sampling_params_priority_orders_queue():
    q = ModelQueue(0)
    lo = Request(rid=0, x=None, arrival_t=0.0, deadline_t=1.0)
    hi = Request(rid=1, x=None, arrival_t=0.0, deadline_t=5.0,
                 params=SamplingParams(priority=3))
    q.push(lo, now=0.0)
    q.push(hi, now=0.0)
    # priority outranks the (much earlier) deadline of the low request
    assert q.pop() is hi and q.pop() is lo


# ---------------------------------------------------------------------------
# Streaming events on the paged path
# ---------------------------------------------------------------------------

def test_streaming_events_match_result(model):
    """Event order is PREFILLING* FIRST_TOKEN TOKEN* FINISHED with
    monotone timestamps, the streamed tokens equal the result() tail,
    and TTFT/ITL land in the metrics snapshot."""
    eng = make_engine(model)
    prompt = prompt_of(9, model=model)
    ref = eng.generate_paged(prompt, max_new_tokens=6)["tokens"]

    async def main():
        sched = PagedLLMScheduler([eng], PagedLLMConfig())
        async with sched:
            handle = sched.submit(
                prompt, SamplingParams(max_new_tokens=6, stream=True))
            evs = [ev async for ev in handle]
            out = await handle.result()
        return sched, out, evs

    sched, out, evs = asyncio.run(main())
    np.testing.assert_array_equal(out, ref)
    types = [e.type for e in evs]
    assert types[0] is EventType.PREFILLING
    assert types[-1] is EventType.FINISHED
    first = types.index(EventType.FIRST_TOKEN)
    assert all(t is EventType.PREFILLING for t in types[:first])
    assert all(t is EventType.TOKEN for t in types[first + 1:-1])
    ts = [e.t for e in evs]
    assert ts == sorted(ts)
    streamed = [e.token for e in evs
                if e.type in (EventType.FIRST_TOKEN, EventType.TOKEN)]
    np.testing.assert_array_equal(streamed, out[len(prompt):])
    assert evs[-1].finish_reason == "length"
    np.testing.assert_array_equal(evs[-1].output, out)
    snap = sched.snapshot()
    assert snap["ttft_p50_ms"] > 0.0
    assert snap["itl_p50_ms"] > 0.0
    assert snap["pools"][0]["pages_in_use"] == 0


def test_non_streaming_handle_rejects_iteration(model):
    eng = make_engine(model)

    async def main():
        sched = PagedLLMScheduler([eng], PagedLLMConfig(max_new_tokens=2))
        async with sched:
            handle = sched.submit(prompt_of(5, model=model))
            with pytest.raises(RuntimeError, match="stream"):
                async for _ in handle:
                    pass
            return await handle        # handles are awaitable

    out = asyncio.run(main())
    assert len(out) == 7


def test_stop_tokens_end_generation_early(model):
    """A sampled stop token terminates the stream with reason "stop"
    and the result is trimmed at the stop token."""
    eng = make_engine(model)
    prompt = prompt_of(7, model=model)
    ref = eng.generate_paged(prompt, max_new_tokens=10)["tokens"]
    stop = int(ref[len(prompt) + 2])     # the 3rd generated token

    async def main():
        sched = PagedLLMScheduler([eng], PagedLLMConfig())
        async with sched:
            handle = sched.submit(prompt, SamplingParams(
                max_new_tokens=10, stop_tokens=(stop,), stream=True))
            evs = [ev async for ev in handle]
            out = await handle
        return out, evs

    out, evs = asyncio.run(main())
    np.testing.assert_array_equal(out, ref[:len(prompt) + 3])
    assert evs[-1].finish_reason == "stop"


# ---------------------------------------------------------------------------
# Chunked prefill
# ---------------------------------------------------------------------------

def test_chunked_prefill_engine_parity(model):
    """The resumable chunk path produces token-identical output to the
    serial whole-prompt prefill, including over a resident shared
    prefix, and a mid-prefill release is a complete rollback."""
    cfg, params = model
    ref_eng = make_engine(model)
    pa = prompt_of(11, fold=1, model=model)
    pb = np.concatenate([pa[:8], prompt_of(9, fold=2, model=model)])
    ref_a = ref_eng.generate_paged(pa, max_new_tokens=5)["tokens"]
    ref_b = ref_eng.generate_paged(pb, max_new_tokens=5)["tokens"]

    eng = make_engine(model)
    sa = eng.begin_prefill(pa, max_new_tokens=5)
    chunks = 0
    while not eng.prefill_chunk(sa, chunk_tokens=PS):
        chunks += 1
    assert chunks >= 2                       # 11 tokens / 4-token chunks
    sb = eng.begin_prefill(pb, max_new_tokens=5)
    eng.prefill_chunk(sb, chunk_tokens=PS)   # first chunk maps lazily
    assert sb.shared_prefix_len == 8         # maps sa's resident prefix
    while not eng.prefill_chunk(sb, chunk_tokens=PS):
        pass
    while not (sa.done and sb.done):
        eng.decode_step_batch([s for s in (sa, sb) if not s.done])
    np.testing.assert_array_equal(np.concatenate([pa, sa.tokens]), ref_a)
    np.testing.assert_array_equal(np.concatenate([pb, sb.tokens]), ref_b)
    eng.pool.release(sa)
    eng.pool.release(sb)
    assert eng.pool.pages_in_use == 0

    # mid-prefill rollback: pages allocated so far all hand back
    sc = eng.begin_prefill(prompt_of(16, fold=3, model=model),
                           max_new_tokens=4)
    eng.prefill_chunk(sc, chunk_tokens=2 * PS)
    assert not sc.prefill_done and eng.pool.pages_in_use > 0
    eng.pool.release(sc)
    assert eng.pool.pages_in_use == 0


def test_chunked_prefill_interleaves_with_decode(model):
    """A long prompt admitted behind a running stream must not stall
    it: with prefill_chunk_pages set, the running request keeps
    emitting TOKEN events *between* the long prompt's PREFILLING
    events, and both outputs equal their serial references."""
    eng = make_engine(model)
    long_p = prompt_of(40, model=model)
    short_p = prompt_of(6, fold=1, model=model)
    ref_long = eng.generate_paged(long_p, max_new_tokens=6)["tokens"]
    ref_short = eng.generate_paged(short_p, max_new_tokens=12)["tokens"]

    async def main():
        sched = PagedLLMScheduler(
            [eng], PagedLLMConfig(prefill_chunk_pages=2))
        sched.warmup([6, 40])
        async with sched:
            hs = sched.submit(short_p, SamplingParams(max_new_tokens=12,
                                                      stream=True))
            while sched.decode_batches < 1:      # short is mid-generation
                await asyncio.sleep(0.002)
            hl = sched.submit(long_p, SamplingParams(max_new_tokens=6,
                                                     stream=True))
            evs_l = [ev async for ev in hl]
            out_l = await hl
            out_s = await hs
            evs_s = [ev async for ev in hs]
        return sched, out_s, out_l, evs_s, evs_l

    sched, out_s, out_l, evs_s, evs_l = asyncio.run(main())
    for evs in (evs_s, evs_l):
        assert sum(e.type is EventType.FIRST_TOKEN for e in evs) == 1
    np.testing.assert_array_equal(out_l, ref_long)
    np.testing.assert_array_equal(out_s, ref_short)
    # 40 tokens at 8-token chunks: >= 4 prefill-progress events
    assert sum(e.type is EventType.PREFILLING for e in evs_l) >= 4
    lp0 = min(e.t for e in evs_l if e.type is EventType.PREFILLING)
    lft = next(e.t for e in evs_l if e.type is EventType.FIRST_TOKEN)
    interleaved = [e for e in evs_s
                   if e.type is EventType.TOKEN and lp0 < e.t < lft]
    assert interleaved, "no short-stream token landed during long prefill"
    snap = sched.snapshot()
    assert snap["prefill_chunks"] >= 5
    assert snap["interleaved_chunks"] >= 1
    assert snap["pools"][0]["pages_in_use"] == 0


def test_chunked_admission_budgets_first_chunk(model):
    """With chunked prefill, a prompt whose WHOLE page span exceeds the
    current free pages still admits on its first chunk and completes as
    running requests retire (serial admission would hold it back)."""
    eng = make_engine(model, num_pages=12, decode_batch=2)  # 11 usable pages
    long_p = prompt_of(28, model=model)      # 28+4 tokens -> 8 pages
    short_p = prompt_of(8, fold=1, model=model)  # 8+4 -> 3 pages
    ref_long = eng.generate_paged(long_p, max_new_tokens=4)["tokens"]
    ref_short = eng.generate_paged(short_p, max_new_tokens=4)["tokens"]

    async def main():
        sched = PagedLLMScheduler(
            [eng], PagedLLMConfig(max_new_tokens=4, prefill_chunk_pages=1))
        async with sched:
            h1 = sched.submit(short_p)
            h2 = sched.submit(long_p)
            return await asyncio.gather(h1, h2)

    out_s, out_l = asyncio.run(main())
    np.testing.assert_array_equal(out_s, ref_short)
    np.testing.assert_array_equal(out_l, ref_long)
    assert eng.pool.pages_in_use == 0


# ---------------------------------------------------------------------------
# Cancellation at every phase
# ---------------------------------------------------------------------------

async def _pool_drains(pool, target=0, tries=400):
    for _ in range(tries):
        if pool.pages_in_use == target:
            return True
        await asyncio.sleep(0.005)
    return False


def test_cancel_every_phase_restores_pool(model):
    """Cancel during queue-wait, mid-chunked-prefill, and mid-decode:
    each resolves the future with CancelledError and returns the pool
    to its pre-admission unique-page count."""
    eng = make_engine(model, decode_batch=2)
    long_p = prompt_of(40, model=model)
    short_p = prompt_of(6, fold=1, model=model)

    async def main():
        sched = PagedLLMScheduler(
            [eng], PagedLLMConfig(max_new_tokens=24, prefill_chunk_pages=1))
        async with sched:
            # ---- mid-decode ----
            h = sched.submit(short_p, stream=True)
            async for ev in h:
                if ev.type is EventType.TOKEN:
                    break
            assert h.cancel()
            assert not h.cancel()                # second cancel is a no-op
            with pytest.raises(asyncio.CancelledError):
                await h
            assert await _pool_drains(eng.pool)

            # ---- mid-chunked-prefill ----
            h = sched.submit(long_p, max_new_tokens=6, stream=True)
            async for ev in h:
                if ev.type is EventType.PREFILLING and ev.prefilled:
                    break
            assert h.cancel()
            with pytest.raises(asyncio.CancelledError):
                await h
            assert await _pool_drains(eng.pool)

            # ---- queue-wait: both decode slots busy, third queues ----
            running = [sched.submit(short_p, max_new_tokens=24)
                       for _ in range(2)]
            queued = sched.submit(short_p, max_new_tokens=4)
            assert queued.cancel()
            with pytest.raises(asyncio.CancelledError):
                await queued
            outs = await asyncio.gather(*running)
            assert all(len(o) == 30 for o in outs)
        return sched

    sched = asyncio.run(main())
    assert eng.pool.pages_in_use == 0
    snap = sched.snapshot()
    assert snap["cancelled"] == 3 and snap["failed"] == 0
    assert snap["arrived"] == (snap["completed"] + snap["failed"]
                               + snap["cancelled"])


def test_join_drops_request_cancelled_during_final_chunk(model):
    """A request cancelled while its final prefill chunk is on the
    executor must not be resurrected by _join: the sequence's pages
    release and it never enters the decode roster (regression for the
    cancel-vs-join race)."""
    eng = make_engine(model)
    sched = PagedLLMScheduler([eng], PagedLLMConfig())
    seq = eng.prefill_into_pages(prompt_of(6, model=model), max_new_tokens=4)
    req = Request(rid=0, x=prompt_of(6, model=model), arrival_t=0.0,
                  deadline_t=1.0)
    assert req.cancel(0.5)
    sched._join(0, req, seq, 0)
    assert len(sched.slots[0]) == 0          # never joined
    assert req.state is RequestState.CANCELLED   # not resurrected
    assert eng.pool.pages_in_use == 0        # pages released


# ---------------------------------------------------------------------------
# Cross-request logit cache
# ---------------------------------------------------------------------------

def test_logit_cache_zero_flop_repeat_admission(model):
    """A fully-resident repeat prompt with a cached final-token logits
    row skips prefill entirely (zero tokens computed), still COWs its
    boundary page on decode, and generates the reference tokens."""
    ref_eng = make_engine(model)
    prompt = prompt_of(10, model=model)      # 10 % 4 != 0: boundary page
    ref = ref_eng.generate_paged(prompt, max_new_tokens=5)["tokens"]

    eng = make_engine(model, logit_cache=4)
    a = eng.prefill_into_pages(prompt, max_new_tokens=5)
    computed = eng.prefill_tokens_computed
    b = eng.prefill_into_pages(prompt, max_new_tokens=5)
    assert eng.logit_cache_hits == 1
    assert eng.prefill_tokens_computed == computed   # zero-FLOP admission
    assert b.prefill_done and b.shared_prefix_len == len(prompt)
    while not (a.done and b.done):
        eng.decode_step_batch([s for s in (a, b) if not s.done])
    np.testing.assert_array_equal(np.concatenate([prompt, a.tokens]), ref)
    np.testing.assert_array_equal(np.concatenate([prompt, b.tokens]), ref)
    assert eng.cow_count == 1                # boundary page still COWed
    eng.pool.release(a)
    eng.pool.release(b)
    assert eng.pool.pages_in_use == 0

    # LRU bound: capacity 4 holds at most 4 entries
    for i in range(6):
        s = eng.prefill_into_pages(prompt_of(6, fold=10 + i, model=model),
                                   max_new_tokens=2)
        eng.pool.release(s)
    assert len(eng._logit_cache) <= 4


def test_logit_cache_counters_in_snapshot(model):
    eng = make_engine(model, logit_cache=8)
    prompt = prompt_of(8, model=model)

    async def main():
        sched = PagedLLMScheduler([eng], PagedLLMConfig(max_new_tokens=3))
        async with sched:
            a = sched.submit(prompt)
            b = sched.submit(prompt)
            await asyncio.gather(a, b)
        return sched.snapshot()

    snap = asyncio.run(main())
    assert snap["logit_cache_hits"] + snap["logit_cache_misses"] >= 1
    assert snap["pools"][0]["pages_in_use"] == 0


# ---------------------------------------------------------------------------
# Mux path: unified handle surface + deadline degrade
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


def test_mux_submit_returns_streaming_handle():
    server = FakeServer()

    async def main():
        sched = MuxScheduler(server, SchedulerConfig(max_batch_size=2,
                                                     max_wait_ms=1.0))
        async with sched:
            h = sched.submit(np.zeros(4, np.float32),
                             SamplingParams(stream=True))
            evs = [ev async for ev in h]
            out = await h.result()
        return sched, out, evs

    sched, out, evs = asyncio.run(main())
    np.testing.assert_array_equal(out, np.zeros(4))
    assert [e.type for e in evs] == [EventType.FINISHED]
    assert sched.metrics.snapshot()["ttft_p50_ms"] > 0.0


def test_mux_cancel_in_queue_skips_bucket():
    server = FakeServer()

    async def main():
        # max_wait so long only the stop-flush drains the queue
        sched = MuxScheduler(server, SchedulerConfig(max_batch_size=64,
                                                     max_wait_ms=60_000.0))
        await sched.start()
        keep = sched.submit(np.full(4, 1.0, np.float32))
        dropped = sched.submit(np.full(4, 1.0, np.float32))
        assert dropped.cancel()
        with pytest.raises(asyncio.CancelledError):
            await dropped
        await sched.stop(drain=True)
        np.testing.assert_array_equal(keep.future.result(), np.full(4, 2.0))
        return sched

    sched = asyncio.run(main())
    snap = sched.metrics.snapshot()
    assert snap["completed"] == 1 and snap["cancelled"] == 1
    assert snap["arrived"] == (snap["completed"] + snap["failed"]
                               + snap["cancelled"])


def test_no_drain_stop_emits_finished_for_streams():
    """stop(drain=False) must fail stranded requests THROUGH the
    request (emitting FINISHED) so a streaming consumer is unblocked
    rather than hanging on an abandoned event queue forever."""
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
        h = sched.submit(np.zeros(4, np.float32), SamplingParams(stream=True))

        async def consume():
            return [ev async for ev in h]

        task = asyncio.create_task(consume())
        await asyncio.sleep(0)               # let the consumer block
        await sched.stop(drain=False)
        evs = await asyncio.wait_for(task, timeout=5.0)   # must not hang
        assert evs[-1].type is EventType.FINISHED
        # the flush may legitimately win the race and complete the
        # request; either way the stream terminates with FINISHED
        if evs[-1].finish_reason == "error":
            with pytest.raises(RuntimeError, match="stopped before"):
                await h
        else:
            assert evs[-1].finish_reason == "complete"
            np.testing.assert_array_equal(await h, np.zeros(4))
        return sched

    sched = asyncio.run(main())
    snap = sched.metrics.snapshot()
    assert snap["arrived"] == (snap["completed"] + snap["failed"]
                               + snap["cancelled"])


def test_deadline_degrade_reroutes_to_cheapest():
    """MDInference hook: when the selected model's estimated service
    time exceeds the request's SLO budget, admission re-routes to the
    cheapest model whose estimate fits.  Off by default."""
    server = FakeServer()
    x_heavy = np.full(4, 2.0, np.float32)     # probe routes to model 2

    async def run(degrade):
        sched = MuxScheduler(server, SchedulerConfig(
            max_batch_size=2, max_wait_ms=1.0, deadline_degrade=degrade))
        # prime the estimator: model 2 is far too slow for a 50ms SLO,
        # models 0/1 easily fit
        sched.metrics._service_ema = [0.001, 0.002, 10.0]
        async with sched:
            out = await sched.submit(x_heavy, slo_ms=50.0)
        return sched, np.asarray(out)

    sched_off, out_off = asyncio.run(run(False))
    np.testing.assert_array_equal(out_off, x_heavy * 3)   # model 2
    assert sched_off.metrics.deadline_degraded == 0

    sched_on, out_on = asyncio.run(run(True))
    np.testing.assert_array_equal(out_on, x_heavy * 1)    # cheapest fitting
    snap = sched_on.metrics.snapshot()
    assert snap["deadline_degraded"] == 1
    assert snap["completed"] == 1
