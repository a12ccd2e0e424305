"""The ModelBackend executor protocol (repro.serving.backend).

Parity matrix: the streaming/cancel/prefix-sharing contract runs
against InProcessBackend, DisaggregatedBackend and RemoteStubBackend —
token-identical events and outputs, zero page leaks on cancellation at
every phase (including mid-transfer).  Plus the satellites that ride
on the backend seam: window-span page reclaim, hard load shedding
(BUDGET_EXCEEDED), probe-path logit-cache prewarming, and the
queue-depth-aware admission estimates."""
import asyncio

import jax
import numpy as np
import pytest

from repro.configs.base import LayerSpec, ModelConfig
from repro.models import transformer as tf
from repro.serving.backend import (BackendCapacity, DisaggregatedBackend,
                                   InProcessBackend, InProcessMuxBackend,
                                   ModelBackend, RemoteStubBackend,
                                   wire_decode, wire_encode)
from repro.serving.engine import Engine, ServeConfig
from repro.serving.kv_cache import OutOfPages
from repro.serving.mux_server import MuxServer
from repro.serving.scheduler import (BUDGET_EXCEEDED, AdmissionController,
                                     BudgetExceeded, EventType, ModelQueue,
                                     MuxScheduler, PagedLLMConfig,
                                     PagedLLMScheduler, Request,
                                     SamplingParams, SchedulerConfig,
                                     SchedulerMetrics)

PS = 4          # page size everywhere here
BACKENDS = ("inproc", "disagg", "remote")


def tiny_config() -> ModelConfig:
    return ModelConfig(name="backend-tiny", arch_type="dense", num_layers=2,
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


def make_backend(model, kind, *, num_pages=40, decode_batch=4,
                 **kw) -> ModelBackend:
    cfg, params = model
    if kind == "inproc":
        return InProcessBackend(make_engine(model, num_pages, decode_batch,
                                            **kw))
    if kind == "disagg":
        return DisaggregatedBackend.build(
            cfg, params, ServeConfig(max_len=64), num_pages=num_pages,
            page_size=PS, decode_batch=decode_batch, **kw)
    if kind == "remote":
        return RemoteStubBackend(InProcessBackend(
            make_engine(model, num_pages, decode_batch, **kw)))
    raise ValueError(kind)


def prompt_of(n, fold=0):
    return np.asarray(jax.random.randint(jax.random.fold_in(
        jax.random.key(5), fold), (n,), 0, tiny_config().vocab_size))


def assert_pools_drained(backend: ModelBackend) -> None:
    s = backend.stats()
    assert s["pool"]["pages_in_use"] == 0, s["pool"]
    if "prefill_pool" in s:
        assert s["prefill_pool"]["pages_in_use"] == 0, s["prefill_pool"]


# ---------------------------------------------------------------------------
# Protocol surface
# ---------------------------------------------------------------------------

def test_bare_backend_fails_loudly():
    b = ModelBackend()
    with pytest.raises(NotImplementedError, match="token-level"):
        b.begin(np.zeros(2), max_new_tokens=1)
    with pytest.raises(NotImplementedError):
        b.capacity()
    assert b.healthy        # default until an implementation says otherwise


def test_wire_schema_round_trips_numpy():
    msg = {"op": "decode", "id": np.int64(3),
           "body": {"sids": np.asarray([1, 2]), "x": np.float32(1.5)}}
    out = wire_decode(wire_encode(msg))
    assert out == {"op": "decode", "id": 3,
                   "body": {"sids": [1, 2], "x": 1.5}}
    with pytest.raises(TypeError, match="wire-serializable"):
        wire_encode({"bad": object()})


# ---------------------------------------------------------------------------
# Parity matrix: streaming / chunked prefill / prefix sharing / cancel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", BACKENDS)
def test_streaming_events_token_identical(model, kind):
    """Event order and streamed tokens match the solo-engine reference
    through every backend — the token-identity acceptance bar."""
    ref = make_engine(model).generate_paged(prompt_of(9),
                                            max_new_tokens=6)["tokens"]
    backend = make_backend(model, kind)

    async def main():
        sched = PagedLLMScheduler(backends=[backend], cfg=PagedLLMConfig())
        async with sched:
            handle = sched.submit(
                prompt_of(9), SamplingParams(max_new_tokens=6, stream=True))
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
    streamed = [e.token for e in evs
                if e.type in (EventType.FIRST_TOKEN, EventType.TOKEN)]
    np.testing.assert_array_equal(streamed, out[9:])
    assert_pools_drained(backend)
    snap = sched.snapshot()
    assert snap["completed"] == 1 and snap["failed"] == 0
    if kind == "disagg":
        assert snap["transfers"] == 1
        assert any(snap["transfer_p50_ms"]) or snap["transfer_count"][0] == 1
    if kind == "remote":
        assert backend.messages_sent > 0


@pytest.mark.parametrize("kind", BACKENDS)
def test_chunked_prefill_and_prefix_sharing_parity(model, kind):
    """Chunked prefill over a shared prefix: a long prompt, a diverging
    sibling and a short stream all produce their solo references, the
    pools drain, and the backend reports the expected machinery (KV
    transfers on disagg, wire traffic on remote)."""
    ref_eng = make_engine(model)
    pa = prompt_of(24, fold=1)
    pb = np.concatenate([pa[:8], prompt_of(9, fold=2)])
    ps = prompt_of(6, fold=3)
    refs = [ref_eng.generate_paged(p, max_new_tokens=5)["tokens"]
            for p in (pa, pb, ps)]
    backend = make_backend(model, kind)

    async def main():
        sched = PagedLLMScheduler(
            backends=[backend], cfg=PagedLLMConfig(prefill_chunk_pages=1))
        sched.warmup([6, 24])
        async with sched:
            handles = [sched.submit(p, max_new_tokens=5)
                       for p in (pa, pb, ps)]
            return sched, await asyncio.gather(*handles)

    sched, outs = asyncio.run(main())
    for out, ref in zip(outs, refs):
        np.testing.assert_array_equal(out, ref)
    assert_pools_drained(backend)
    snap = sched.snapshot()
    assert snap["prefill_chunks"] >= 6        # 24 tokens at 4-token chunks
    if kind != "remote":
        # pb maps pa's resident 8-token prefix (remote admission is
        # conservative but server-side sharing still runs; its counter
        # is asserted through stats below either way)
        assert snap["prefill_tokens_shared"] >= 8
    if kind == "disagg":
        assert snap["transfers"] == 3
    elif kind == "inproc":
        assert snap["transfers"] == 0


@pytest.mark.parametrize("kind", BACKENDS)
def test_cancel_every_phase_restores_pools(model, kind):
    """Cancel during queue-wait, mid-chunked-prefill and mid-decode
    against every backend: the future resolves with CancelledError and
    every pool involved returns to zero held pages."""
    backend = make_backend(model, kind, decode_batch=2)
    long_p, short_p = prompt_of(40), prompt_of(6, fold=1)

    async def drained(target=0, tries=400):
        for _ in range(tries):
            s = backend.stats()
            held = s["pool"]["pages_in_use"] + \
                s.get("prefill_pool", {"pages_in_use": 0})["pages_in_use"]
            if held == target:
                return True
            await asyncio.sleep(0.005)
        return False

    async def main():
        sched = PagedLLMScheduler(
            backends=[backend],
            cfg=PagedLLMConfig(max_new_tokens=24, prefill_chunk_pages=1))
        async with sched:
            # ---- mid-decode ----
            h = sched.submit(short_p, stream=True)
            async for ev in h:
                if ev.type is EventType.TOKEN:
                    break
            assert h.cancel()
            with pytest.raises(asyncio.CancelledError):
                await h
            assert await drained()

            # ---- mid-chunked-prefill (and mid-transfer on disagg:
            # the cancel lands while chunks/transfer are in flight) ----
            h = sched.submit(long_p, max_new_tokens=6, stream=True)
            async for ev in h:
                if ev.type is EventType.PREFILLING and ev.prefilled:
                    break
            assert h.cancel()
            with pytest.raises(asyncio.CancelledError):
                await h
            assert await drained()

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
    assert_pools_drained(backend)
    snap = sched.snapshot()
    assert snap["cancelled"] == 3 and snap["failed"] == 0
    assert snap["arrived"] == (snap["completed"] + snap["failed"]
                               + snap["cancelled"])


def test_disagg_transfer_backpressure_and_cancel_leak_free(model):
    """Mid-transfer OutOfPages (decode pool full) is backpressure with
    nothing held: prefill pages are already released, no decode page is
    allocated, and releasing the sequence mid-transfer drops the
    staged package without leaking either pool."""
    cfg, params = model
    backend = DisaggregatedBackend.build(
        cfg, params, ServeConfig(max_len=64), num_pages=4,  # 3 allocatable
        page_size=PS, decode_batch=2, prefill_pages=40)

    async def main():
        await backend.start()
        try:
            seq = backend.begin(prompt_of(12), max_new_tokens=8)  # 5 pages
            with pytest.raises(OutOfPages):
                while not await backend.prefill_chunk(seq, chunk_tokens=PS):
                    pass
            # sealed on the prefill side, stuck before the scatter:
            assert seq.prefill_done
            assert seq.transfer_package is not None
            s = backend.stats()
            assert s["prefill_pool"]["pages_in_use"] == 0   # gather released
            assert s["pool"]["pages_in_use"] == 0           # alloc rolled back
            backend.release(seq)                             # cancel mid-transfer
            assert seq.transfer_package is None
        finally:
            await backend.stop()

    asyncio.run(main())
    assert_pools_drained(backend)


def test_disagg_rejects_mismatched_geometry(model):
    cfg, params = model
    a = make_engine(model)
    b = Engine(cfg, params, ServeConfig(max_len=32))
    b.init_paged(num_pages=10, page_size=PS)
    with pytest.raises(ValueError, match="page_size and\n?\\s*max_len"):
        DisaggregatedBackend(a, b)


def test_disagg_transfer_queue_is_deadline_ordered(model):
    """EDF at the transfer turnstile: while one KV transfer occupies
    the decode executor, later-sealed-but-tighter deadlines overtake
    earlier lax ones.  Seal order D, A(lax), B(tight) must dispatch
    D, B, A — the regression this pins is FIFO dispatch (D, A, B)."""
    import time as _time

    backend = make_backend(model, "disagg", num_pages=40)

    async def main():
        await backend.start()
        try:
            now = _time.monotonic()
            seqs = {}
            for rid, deadline in (("D", None), ("A", now + 100.0),
                                  ("B", now + 0.5)):
                seq = backend.begin(prompt_of(8, fold=ord(rid)),
                                    max_new_tokens=2)
                seq.trace_rid = rid
                if deadline is not None:
                    seq.deadline_t = deadline
                seqs[rid] = seq
            # wedge the decode executor so D's scatter holds the
            # turnstile while A and B queue behind it
            stall = asyncio.ensure_future(
                backend._run("decode", _time.sleep, 0.6))
            await asyncio.sleep(0.05)
            tasks = []
            for rid in ("D", "A", "B"):
                tasks.append(asyncio.ensure_future(
                    backend.prefill_chunk(seqs[rid])))
                await asyncio.sleep(0.05)   # D reaches the gate first
            await asyncio.gather(stall, *tasks)
            assert backend.transfer_log == ["D", "B", "A"]
            for seq in seqs.values():
                backend.release(seq)
        finally:
            await backend.stop()

    asyncio.run(main())
    assert_pools_drained(backend)


# ---------------------------------------------------------------------------
# Satellite: window/chunked span reclaim
# ---------------------------------------------------------------------------

def swa_config() -> ModelConfig:
    return ModelConfig(name="swa-tiny", arch_type="dense", num_layers=2,
                       d_model=32, d_ff=64, vocab_size=64,
                       pattern=(LayerSpec(attn_kind="swa"),), window=8,
                       num_heads=4, num_kv_heads=2, head_dim=8,
                       compute_dtype="float32", param_dtype="float32",
                       kv_cache_dtype="float32")


def test_span_reclaim_frees_out_of_window_pages():
    """All-banded model: pages wholly below the window decref during
    decode, resident pages stay O(window) instead of O(len), and the
    generation is token-identical to the no-reclaim engine."""
    cfg = swa_config()
    params = tf.init_params(cfg, jax.random.key(0))
    scfg = ServeConfig(max_len=128)
    base = Engine(cfg, params, scfg)
    base.init_paged(num_pages=40, page_size=PS, decode_batch=2,
                    span_reclaim=False)
    rec = Engine(cfg, params, scfg)
    rec.init_paged(num_pages=40, page_size=PS, decode_batch=2)

    prompt = np.asarray(jax.random.randint(jax.random.key(9), (6,), 0,
                                           cfg.vocab_size))
    held_base, held_rec = [], []
    seqs = {}
    for eng, held in ((base, held_base), (rec, held_rec)):
        seq = eng.prefill_into_pages(prompt, max_new_tokens=40)
        seqs[id(eng)] = seq
        while not seq.done:
            eng.decode_step_batch([seq])
            held.append(eng.pool.pages_in_use)
    np.testing.assert_array_equal(seqs[id(base)].tokens,
                                  seqs[id(rec)].tokens)
    assert base.reclaimed_pages == 0 and rec.reclaimed_pages > 0
    # 6 + 40 tokens = 12 pages stay resident without reclaim; with it
    # the tail of the run holds only the window's worth (+ the page
    # being written): ceil(8/4) + 1 = 3
    assert held_base[-1] == 12
    assert held_rec[-1] <= 3
    for eng in (base, rec):
        eng.pool.release(seqs[id(eng)])
        assert eng.pool.pages_in_use == 0      # None slots skipped cleanly


def test_span_reclaim_noop_with_full_layer(model):
    """Any full-attention layer pins the whole context: nothing may be
    reclaimed (the block table is shared across layers)."""
    eng = make_engine(model)           # default pattern: full attention
    assert eng._layer_spans is None
    seq = eng.prefill_into_pages(prompt_of(6), max_new_tokens=20)
    while not seq.done:
        eng.decode_step_batch([seq])
    assert eng.reclaimed_pages == 0
    eng.pool.release(seq)
    assert eng.pool.pages_in_use == 0


def test_span_reclaim_keeps_pool_pressure_bounded_across_requests():
    """The freed pages are immediately reusable: a pool too small to
    hold two full-length windowed generations still serves them
    concurrently through the scheduler."""
    cfg = swa_config()
    params = tf.init_params(cfg, jax.random.key(0))
    eng = Engine(cfg, params, ServeConfig(max_len=128))
    eng.init_paged(num_pages=40, page_size=PS, decode_batch=2)
    prompt = np.asarray(jax.random.randint(jax.random.key(9), (6,), 0,
                                           cfg.vocab_size))
    ref = eng.generate_paged(prompt, max_new_tokens=40)["tokens"]

    async def main():
        sched = PagedLLMScheduler([eng], PagedLLMConfig())
        async with sched:
            outs = await asyncio.gather(
                sched.submit(prompt, max_new_tokens=40, seed=0),
                sched.submit(prompt, max_new_tokens=40, seed=0))
        return outs

    for out in asyncio.run(main()):
        np.testing.assert_array_equal(out, ref)
    assert eng.pool.pages_in_use == 0
    assert eng.reclaimed_pages > 0


# ---------------------------------------------------------------------------
# Satellite: hard load shedding (BUDGET_EXCEEDED)
# ---------------------------------------------------------------------------

class FakeServer:
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


def test_load_shed_fails_fast_with_budget_exceeded():
    """When no model — selected or degraded — can meet the SLO budget,
    shed_on_overload fails the request at admission with
    BUDGET_EXCEEDED instead of queueing a certain miss; without the
    flag the degraded request still queues and serves."""
    async def run(shed):
        sched = MuxScheduler(FakeServer(), SchedulerConfig(
            max_batch_size=2, max_wait_ms=1.0, deadline_degrade=True,
            shed_on_overload=shed))
        sched.metrics._service_ema = [10.0, 10.0, 10.0]   # nobody fits 50ms
        async with sched:
            h = sched.submit(np.full(4, 2.0, np.float32),
                             SamplingParams(stream=True), slo_ms=50.0)
            evs = [ev async for ev in h]
            try:
                out = await h
                exc = None
            except BudgetExceeded as e:
                out, exc = None, e
        return sched, out, exc, evs

    sched, out, exc, evs = asyncio.run(run(True))
    assert out is None and isinstance(exc, BudgetExceeded)
    assert exc.status == "BUDGET_EXCEEDED"
    assert evs[-1].type is EventType.FINISHED
    assert evs[-1].finish_reason == BUDGET_EXCEEDED
    snap = sched.metrics.snapshot()
    assert snap["budget_exceeded"] == 1 and snap["failed"] == 1
    assert snap["arrived"] == (snap["completed"] + snap["failed"]
                               + snap["cancelled"])

    sched, out, exc, _evs = asyncio.run(run(False))
    assert exc is None
    np.testing.assert_array_equal(out, np.full(4, 2.0))   # degraded to m=0
    assert sched.metrics.snapshot()["budget_exceeded"] == 0


def test_queue_depth_scales_service_estimate():
    """The admission estimate is EMA * (1 + batches of work ahead):
    queued requests count in whole buckets from the backend's
    capacity, so a deep queue degrades before an idle one."""
    server = FakeServer(n=1)
    queue = ModelQueue(0)
    metrics = SchedulerMetrics(costs=[1.0])
    metrics._service_ema = [0.1]
    ctrl = AdmissionController(
        server, [queue], metrics, clock=lambda: 0.0,
        backends=[InProcessMuxBackend(server, 0, bucket_capacity=2)])
    assert ctrl.service_estimate(0) == pytest.approx(0.1)
    for rid in range(4):
        queue.push(Request(rid=rid, x=np.zeros(2), arrival_t=0.0,
                           deadline_t=1.0), now=0.0)
    # 4 live requests in buckets of 2 -> 2 batches ahead
    assert ctrl.service_estimate(0) == pytest.approx(0.1 * 3)
    assert queue.live_depth() == 4
    # cancel-in-place: the scheduler discounts the O(1) counter when
    # the cancel lands, and the eventual drain pop must not discount
    # the same entry twice
    queue.peek().cancel(0.5)
    queue.discount_live()
    assert queue.live_depth() == 3
    popped = queue.pop()                       # the cancelled leftover
    assert popped.is_terminal
    assert queue.live_depth() == 3
    assert not queue.pop().is_terminal         # a live one: discounted
    assert queue.live_depth() == 2


# ---------------------------------------------------------------------------
# Satellite: probe-path logit-cache prewarming
# ---------------------------------------------------------------------------

def test_engine_prewarm_makes_repeat_admission_zero_flop(model):
    eng = make_engine(model, logit_cache=4)
    prompt = prompt_of(10)
    ref = make_engine(model).generate_paged(prompt,
                                            max_new_tokens=5)["tokens"]
    row = eng.prewarm_logits(prompt)
    assert row is not None
    assert eng.logit_cache_misses == 1
    computed = eng.prefill_tokens_computed
    assert eng.prewarm_logits(prompt) is not None          # idempotent
    assert eng.prefill_tokens_computed == computed
    seq = eng.prefill_into_pages(prompt, max_new_tokens=5)
    assert eng.logit_cache_hits >= 1                       # zero-FLOP admit
    assert eng.prefill_tokens_computed == computed
    while not seq.done:
        eng.decode_step_batch([seq])
    np.testing.assert_array_equal(np.concatenate([prompt, seq.tokens]), ref)
    eng.pool.release(seq)
    assert eng.shed_prewarmed() == 1
    assert eng.pool.pages_in_use == 0


def test_prewarm_sheds_under_admission_pressure(model):
    """Prewarmed residents are a cache: when a real admission cannot
    fit, the backend sheds them and admits."""
    eng = make_engine(model, num_pages=9, logit_cache=4)   # 8 allocatable
    backend = InProcessBackend(eng)
    assert eng.prewarm_logits(prompt_of(14)) is not None   # holds 4 pages
    big = prompt_of(20, fold=1)                            # 20+4 -> 6 pages
    assert backend.admissible(big, 4)
    assert len(eng._prewarmed) == 0                        # shed to fit
    assert eng.pool.pages_in_use == 0


def test_mux_server_probe_prewarms_selected_engine(model):
    """MuxServer.probe inserts into the selected engine's logit LRU:
    probe-then-admit traffic pays the prompt prefill once."""
    import jax.numpy as jnp
    eng = make_engine(model, logit_cache=4)
    server = MuxServer(mux_params={}, model_fns=[lambda b: b],
                       model_costs=[1.0], engines=[eng])
    server._weights = lambda x: jnp.ones((x.shape[0], 1))  # pre-jit patch
    prompt = prompt_of(8)
    res = server.probe(prompt[None])
    np.testing.assert_array_equal(res["assign"], [0])
    assert eng.logit_cache_misses == 1
    computed = eng.prefill_tokens_computed
    seq = eng.prefill_into_pages(prompt, max_new_tokens=3)
    assert eng.logit_cache_hits == 1
    assert eng.prefill_tokens_computed == computed         # zero-FLOP admit
    eng.pool.release(seq)
    eng.shed_prewarmed()
    assert eng.pool.pages_in_use == 0


def test_scheduler_probe_then_admit_hits_in_snapshot(model):
    eng = make_engine(model, logit_cache=8)
    prompt = prompt_of(8)

    async def main():
        sched = PagedLLMScheduler([eng], PagedLLMConfig(max_new_tokens=3))
        async with sched:
            await sched.backends[0].probe(prompt)
            out = await sched.submit(prompt)
        return sched.snapshot(), out

    snap, out = asyncio.run(main())
    assert snap["logit_cache_hits"] >= 1
    assert len(out) == 11
    eng.shed_prewarmed()
    assert eng.pool.pages_in_use == 0


# ---------------------------------------------------------------------------
# Backend capacity introspection
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", BACKENDS)
def test_capacity_reports_pool_geometry(model, kind):
    backend = make_backend(model, kind)
    cap = backend.capacity()
    assert isinstance(cap, BackendCapacity)
    assert cap.decode_batch == 4
    assert cap.page_size == PS
    assert cap.num_pages == 39
    assert cap.free_pages == 39
    assert cap.max_len == 64
    assert backend.fits_ever(30, 20)
    assert not backend.fits_ever(300, 20)
    assert backend.healthy
