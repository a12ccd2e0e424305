"""ModelBackend — the scheduler ⇄ execution seam.

The schedulers in ``repro.serving.scheduler.runtime`` used to call
``Engine`` / ``MuxServer`` methods directly from their worker loops,
which blocked the ROADMAP's next steps (disaggregated prefill/decode
workers, multi-host per-model dispatch) on an API boundary that did
not exist.  This module is that boundary: a backend owns *where and
how* one model's device work runs — its executors, queues and pools —
while the scheduler keeps owning *what* runs when (admission, EDF
chunk ordering, the continuous decode sweep, cancellation).

The executor surface (all device work is ``await``-ed):

    begin(prompt, ...)        host-side admission -> a sequence handle
    await prefill_chunk(seq)  one prefill chunk; True once sealed
    await decode_batch(seqs)  one token for every running sequence
    await probe(prompt)       score/prewarm the model on one prompt
    await step(bucket)        one request-level model step (mux path)
    release(seq)              hand back everything the sequence holds
    admissible()/fits_ever()/capacity()/healthy   admission introspection

A sequence handle must expose the fields the token-level scheduler
reads: ``prompt_len``, ``prefill_pos``, ``shared_prefix_len``,
``prefill_done``, ``tokens``, ``pos``, ``done``, ``finish_reason``.
``PagedSequence`` satisfies this natively; ``RemoteStubBackend`` keeps
a client-side mirror in sync over its wire protocol.

Three implementations ship:

  * ``InProcessBackend`` — wraps one paged ``Engine`` on a single-
    thread executor.  Token-identical to the pre-backend code paths.
  * ``DisaggregatedBackend`` — separate prefill and decode engines
    (same params, private pools) on separate single-thread executors.
    Prefill chunks and decode sweeps run *concurrently*; a sealed
    prefill's KV pages move to the decode pool through a two-stage
    transfer (gather on the prefill executor, alloc+scatter on the
    decode executor — the in-process stand-in for a NIC/ICI copy), so
    a long prefill never stalls the running decode batch.
  * ``RemoteStubBackend`` — serialized request/response over an
    in-process duplex channel with a JSON wire schema.  The seam where
    real RPC/mesh dispatch plugs in: the scheduler side only ever sees
    the wire types, and the server side drives any inner backend.
"""
from __future__ import annotations

import asyncio
import dataclasses
import heapq
import itertools
import json
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.serving.kv_cache import OutOfPages
from repro.serving.observability.tracer import NULL_TRACER, backend_track


class BackendLost(RuntimeError):
    """The backend serving a sequence is gone (transport died, host
    evicted).  Only the requests in flight on that backend fail — with
    the ``BACKEND_LOST`` finish reason — while siblings keep serving;
    contrast with a poisoned-cache failure, which kills the worker."""


@dataclasses.dataclass
class BackendCapacity:
    """One backend's serving capacity, as admission sees it.

    ``decode_batch`` is sequences per decode call (bucket rows on the
    mux path).  Page fields are zero for non-paged backends.
    ``inflight`` counts device calls queued or running on the
    backend's executors — the queue-depth signal the admission
    controller folds into its service-time estimates."""
    decode_batch: int
    page_size: int = 0
    num_pages: int = 0          # allocatable pages (scratch excluded)
    free_pages: int = 0
    cow_headroom: int = 0
    max_len: int = 0
    inflight: int = 0


class ModelBackend:
    """Abstract executor surface for one model.  See module docstring
    for the contract; every method below raises until an
    implementation provides it, so a scheduler driving a backend that
    lacks a surface fails loudly, not silently."""

    name: str = "backend"
    #: tracing default: the shared no-op singleton, so an unbound
    #: backend traces nothing at zero cost
    _tracer = NULL_TRACER
    #: True when prefill and decode run on independent executors, so
    #: the scheduler may leave a prefill chunk in flight while it
    #: keeps sweeping the decode batch.
    concurrent_prefill: bool = False

    # ---- lifecycle ----------------------------------------------------
    async def start(self) -> None:
        """Bring up executors/channels.  Must be restartable."""

    async def stop(self) -> None:
        """Drain and shut down executors/channels (wait for in-flight
        device work; the scheduler reclaims pool state only after)."""

    def bind_metrics(self, metrics, model_id: int) -> None:
        """Attach the scheduler's metrics registry; backends feed
        per-backend queue-wait and transfer timings through it."""
        self._metrics = metrics
        self._model_id = model_id

    def bind_tracer(self, tracer) -> None:
        """Attach the scheduler's tracer.  Backends emit one span per
        device call on their executor tracks (and KV-transfer spans,
        disaggregated); implementations that own engines/pools also
        hand the tracer down so COW/reclaim/alloc instants record."""
        self._tracer = tracer

    # ---- token-level surface ------------------------------------------
    def begin(self, prompt, *, max_new_tokens: int,
              seed: Optional[int] = None,
              temperature: Optional[float] = None,
              stop_tokens: Sequence[int] = ()) -> Any:
        raise NotImplementedError(f"{self.name} has no token-level surface")

    async def prefill_chunk(self, seq, *,
                            chunk_tokens: Optional[int] = None) -> bool:
        raise NotImplementedError(f"{self.name} has no token-level surface")

    async def decode_batch(self, seqs: Sequence) -> np.ndarray:
        raise NotImplementedError(f"{self.name} has no token-level surface")

    def release(self, seq) -> None:
        raise NotImplementedError(f"{self.name} has no token-level surface")

    async def probe(self, prompt):
        """Score one prompt on this backend's model (and, where the
        implementation supports it, prewarm caches so a follow-up
        admission of the same prompt is cheap)."""
        raise NotImplementedError(f"{self.name} has no probe surface")

    # ---- request-level surface (mux path) -----------------------------
    async def step(self, bucket) -> np.ndarray:
        raise NotImplementedError(f"{self.name} has no request-level surface")

    # ---- admission introspection --------------------------------------
    def capacity(self) -> BackendCapacity:
        raise NotImplementedError

    def admission_cost(self, prompt, max_new_tokens: int, *,
                       chunk_tokens: Optional[int] = None
                       ) -> Tuple[int, int]:
        """(pages a fresh admission allocates now, copy-on-write
        headroom to hold back).  Conservative default: the full page
        span with no sharing discount."""
        cap = self.capacity()
        p = int(np.asarray(prompt).reshape((-1,)).shape[0])
        span = p + max_new_tokens
        if chunk_tokens is not None and chunk_tokens < p:
            span = chunk_tokens
        return -(-span // cap.page_size), 0

    def admissible(self, prompt, max_new_tokens: int, *,
                   chunk_tokens: Optional[int] = None) -> bool:
        need, extra = self.admission_cost(prompt, max_new_tokens,
                                          chunk_tokens=chunk_tokens)
        cap = self.capacity()
        return need + cap.cow_headroom + extra <= cap.free_pages

    def fits_ever(self, prompt_len: int, max_new_tokens: int) -> bool:
        cap = self.capacity()
        return (-(-(prompt_len + max_new_tokens) // cap.page_size)
                <= cap.num_pages)

    def set_lazy_decode_alloc(self, enabled: bool) -> None:
        """Push the scheduler's ``lazy_decode_alloc`` policy down to the
        paged engine(s).  No-op by default — request-level backends hold
        no pages to reserve lazily."""

    @property
    def healthy(self) -> bool:
        return True

    # ---- warmup / reporting -------------------------------------------
    def warmup(self, prompt_lens: Sequence[int],
               chunk_tokens: Optional[int] = None) -> None:
        """Compile serving shapes before traffic (control-plane; runs
        before ``start``)."""

    def stats(self) -> Dict[str, Any]:
        return {"name": self.name, "healthy": self.healthy}

    def prefix_digest(self, cap: int = 2048) -> List[str]:
        """Truncated-hex chunk keys this backend's pools hold (device
        ``PrefixIndex`` + host tier) — gossiped in cluster status
        replies so the router can score prefix-aware placement.
        Backends without a paged pool advertise nothing."""
        return []

    # ---- shared helpers ----------------------------------------------
    def _note_queue_wait(self, seconds: float) -> None:
        m = getattr(self, "_metrics", None)
        if m is not None:
            m.on_backend_queue_wait(self._model_id, seconds)

    def _note_transfer(self, seconds: float) -> None:
        m = getattr(self, "_metrics", None)
        if m is not None:
            m.on_transfer(self._model_id, seconds)


class _ExecutorMixin:
    """One named single-thread executor + the await/queue-wait plumbing
    shared by the in-process backends.  Device calls to one executor
    serialize (jit-donated caches must never race), while calls on
    *different* executors — and different backends — overlap."""

    def _init_executors(self, names: Sequence[str]) -> None:
        self._executor_names = list(names)
        self._pools: Dict[str, Optional[ThreadPoolExecutor]] = {
            n: None for n in names}
        self._inflight = 0

    async def start(self) -> None:
        for n in self._executor_names:
            if self._pools[n] is None:
                self._pools[n] = ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix=f"{self.name}-{n}")

    async def stop(self) -> None:
        for n in self._executor_names:
            pool, self._pools[n] = self._pools[n], None
            if pool is not None:
                pool.shutdown(wait=True)

    async def _run(self, executor: str, fn, *args, op: Optional[str] = None):
        pool = self._pools[executor]
        if pool is None:
            raise RuntimeError(
                f"backend {self.name!r} is not started: no {executor} "
                f"executor (await backend.start(), or run it under a "
                f"scheduler)")
        loop = asyncio.get_running_loop()
        t_submit = time.monotonic()

        def wrapped():
            queued = time.monotonic() - t_submit
            self._note_queue_wait(queued)
            tracer = self._tracer
            if not tracer.enabled:
                return fn(*args)
            # executor-occupancy span: runs on the executor thread
            # itself, which the lock-free ring allows
            with tracer.region(
                    "repro.executor.run", backend_track(self.name, executor),
                    op=op or getattr(fn, "__name__", "call"),
                    queued_us=queued * 1e6):
                return fn(*args)

        self._inflight += 1
        try:
            return await loop.run_in_executor(pool, wrapped)
        finally:
            self._inflight -= 1


def _engine_warmup(engine, prompt_lens: Sequence[int],
                   chunk_tokens: Optional[int]) -> None:
    """Compile one paged engine's serving shapes: prefill at each
    padded prompt length (plus an identical twin so the traced-offset
    tail path and the fused copy-on-write decode program compile when
    sharing is on), the decode step, and — chunked mode — the fixed
    chunk shape.
    Warmup pages always hand back; the logit cache is bypassed and
    cleared so synthetic prompts neither skip the compiles nor leave
    entries behind."""
    cache_cap = engine._logit_cache_cap
    engine._logit_cache_cap = 0
    try:
        if chunk_tokens is not None:
            pl = min(2 * chunk_tokens, engine.scfg.max_len - 2)
            if pl > chunk_tokens:
                try:
                    seq = engine.begin_prefill(np.zeros((pl,), np.int32),
                                               max_new_tokens=2)
                    try:
                        while not engine.prefill_chunk(
                                seq, chunk_tokens=chunk_tokens):
                            pass
                    finally:
                        engine.pool.release(seq)
                except OutOfPages:
                    pass            # pool too small: compile on first use
        for pl in sorted(set(
                min(engine.pool.pages_for(p) * engine.pool.page_size,
                    engine.scfg.max_len - 2)
                for p in prompt_lens)):
            if pl < 1:
                continue
            seq = engine.prefill_into_pages(np.zeros((pl,), np.int32),
                                            max_new_tokens=2)
            twin = None
            if engine.pool.prefix_sharing:
                try:
                    twin = engine.prefill_into_pages(
                        np.zeros((pl,), np.int32), max_new_tokens=2)
                except OutOfPages:
                    pass
            try:
                engine.decode_step_batch([seq])
                if twin is not None:
                    # the twin made the first step COW, compiling the
                    # fused-COW decode program; step again on the now-
                    # private page so the plain decode program also
                    # compiles during warmup rather than mid-serve
                    engine.decode_step_batch([seq])
            except OutOfPages:
                pass                # warmup COW found no free page
            finally:
                engine.pool.release(seq)
                if twin is not None:
                    engine.pool.release(twin)
    finally:
        engine._logit_cache_cap = cache_cap
        engine._logit_cache.clear()
        engine.logit_cache_hits = 0
        engine.logit_cache_misses = 0


class InProcessBackend(_ExecutorMixin, ModelBackend):
    """One paged ``Engine`` behind the backend protocol.

    Token-identical to the scheduler calling the engine directly: the
    same jitted entry points run, serialized on one executor thread
    exactly as the pre-backend worker serialized them."""

    #: executor that serializes with decode — speculative verify steps
    #: (``spec_decode.SpeculativeBackend``) must run there
    verify_executor = "device"

    def __init__(self, engine, name: Optional[str] = None):
        if engine.pool is None:   # not an assert: must survive python -O
            raise ValueError(
                "InProcessBackend needs a paged engine: call "
                "Engine.init_paged(num_pages=..., page_size=...) first")
        self.engine = engine
        self.name = name or f"inproc:{engine.cfg.name}"
        self._init_executors(["device"])

    @property
    def verify_engine(self):
        """The engine whose paged caches multi-token verify steps run
        against (the speculative-decoding verify surface)."""
        return self.engine

    def bind_tracer(self, tracer) -> None:
        super().bind_tracer(tracer)
        self.engine.tracer = tracer
        self.engine.trace_track = backend_track(self.name, "engine")
        self.engine.pool.tracer = tracer
        self.engine.pool.trace_track = backend_track(self.name, "pool")

    # ---- token-level ---------------------------------------------------
    def begin(self, prompt, *, max_new_tokens, seed=None, temperature=None,
              stop_tokens=()):
        return self.engine.begin_prefill(
            prompt, max_new_tokens=max_new_tokens, seed=seed,
            temperature=temperature, stop_tokens=stop_tokens)

    async def prefill_chunk(self, seq, *, chunk_tokens=None) -> bool:
        return await self._run(
            "device", lambda: self.engine.prefill_chunk(
                seq, chunk_tokens=chunk_tokens), op="prefill_chunk")

    async def decode_batch(self, seqs):
        return await self._run("device", self.engine.decode_step_batch, seqs,
                               op="decode_step")

    def release(self, seq) -> None:
        if seq.pages:
            self.engine.pool.release(seq)
        seq.pages = []

    async def probe(self, prompt):
        return await self._run("device", self.engine.prewarm_logits, prompt,
                               op="probe")

    def set_lazy_decode_alloc(self, enabled: bool) -> None:
        self.engine.set_lazy_decode_alloc(enabled)

    # ---- admission -----------------------------------------------------
    def capacity(self) -> BackendCapacity:
        # reclaimable pages (the tiered pool's cold retained prefixes)
        # count as free: admission pressure spills them to host instead
        # of rejecting the request
        pool = self.engine.pool
        return BackendCapacity(
            decode_batch=self.engine.decode_batch, page_size=pool.page_size,
            num_pages=pool.num_pages - 1,
            free_pages=pool.num_free + pool.reclaimable_pages,
            cow_headroom=pool.cow_headroom, max_len=self.engine.scfg.max_len,
            inflight=self._inflight)

    def admission_cost(self, prompt, max_new_tokens, *, chunk_tokens=None):
        return self.engine.admission_page_cost(prompt, max_new_tokens,
                                               chunk_tokens=chunk_tokens)

    def admissible(self, prompt, max_new_tokens, *, chunk_tokens=None):
        ok = super().admissible(prompt, max_new_tokens,
                                chunk_tokens=chunk_tokens)
        if not ok and self.engine.shed_prewarmed():
            # probe-prewarmed residents are a cache, not a commitment:
            # under page pressure they yield to real admissions
            ok = super().admissible(prompt, max_new_tokens,
                                    chunk_tokens=chunk_tokens)
        return ok

    @property
    def healthy(self) -> bool:
        return not self.engine.caches_poisoned

    # ---- warmup / reporting -------------------------------------------
    def warmup(self, prompt_lens, chunk_tokens=None):
        _engine_warmup(self.engine, prompt_lens, chunk_tokens)

    def prefix_digest(self, cap: int = 2048) -> List[str]:
        return self.engine.pool.chunk_digest(cap)

    def stats(self) -> Dict[str, Any]:
        e = self.engine
        return {
            "name": self.name, "healthy": self.healthy,
            "pool": e.pool.stats(),
            "prefill_tokens_computed": e.prefill_tokens_computed,
            "prefill_tokens_shared": e.prefill_tokens_shared,
            "cow_copies": e.cow_count,
            "reclaimed_pages": e.reclaimed_pages,
            "logit_cache_hits": e.logit_cache_hits,
            "logit_cache_misses": e.logit_cache_misses,
        }


class InProcessMuxBackend(_ExecutorMixin, ModelBackend):
    """One mux-zoo model (``server.model_step(m, ...)``) behind the
    backend protocol — the request-level counterpart of
    ``InProcessBackend``.  ``capacity().decode_batch`` reports the
    bucket capacity and ``inflight`` the queued device calls, which is
    what makes the admission controller's service estimates
    queue-depth-aware."""

    def __init__(self, server, model_id: int, *, bucket_capacity: int,
                 name: Optional[str] = None):
        self.server = server
        self.model_id = model_id
        self.bucket_capacity = bucket_capacity
        self.name = name or f"mux:{model_id}"
        self._init_executors(["device"])

    async def step(self, bucket) -> np.ndarray:
        return await self._run(
            "device",
            lambda: np.asarray(self.server.model_step(self.model_id, bucket)),
            op="step")

    async def probe(self, bucket):
        return await self._run(
            "device", lambda: np.asarray(self.server.probe_weights(bucket)),
            op="probe")

    def capacity(self) -> BackendCapacity:
        return BackendCapacity(decode_batch=self.bucket_capacity,
                               inflight=self._inflight)


# ===========================================================================
# Disaggregated prefill/decode
# ===========================================================================

class DisaggregatedBackend(_ExecutorMixin, ModelBackend):
    """Separate prefill and decode executors over separate engines.

    The prefill engine owns a (typically smaller) staging pool; the
    decode engine owns the serving pool.  ``prefill_chunk`` runs on the
    prefill executor, ``decode_batch`` on the decode executor, and
    because ``concurrent_prefill`` is True the scheduler leaves chunks
    in flight while it keeps sweeping the decode batch — a long prompt
    inflates nobody else's inter-token latency.

    When a prefill seals, its KV pages move pools in two serialized
    stages (the in-process stand-in for a NIC/ICI transfer):

      gather   (prefill executor)  the sequence's pages are gathered
               out of the prefill cache into a standalone package and
               the prefill pages release immediately
      scatter  (decode executor)   pages allocate in the decode pool
               (OutOfPages here is plain backpressure — nothing is
               held, the package retries after decode frees) and the
               package scatters into the decode cache

    A cancel that lands mid-transfer leaks nothing: before the gather
    the sequence holds prefill pages (released by ``release``), after
    it only the host-side package (dropped by ``release``), after the
    scatter decode pages (released by ``release``).  Outputs are
    token-identical to ``InProcessBackend``: the same jits run on the
    same params, and the transfer copies raw stored KV (quantized
    representation included) bit-for-bit."""

    concurrent_prefill = True
    #: speculative verify serializes with decode on the decode executor
    verify_executor = "decode"

    def __init__(self, prefill_engine, decode_engine,
                 name: Optional[str] = None):
        import jax

        for label, e in (("prefill", prefill_engine),
                         ("decode", decode_engine)):
            if e.pool is None:
                raise ValueError(f"the {label} engine needs a paged pool: "
                                 f"call Engine.init_paged first")
        if (prefill_engine.pool.page_size != decode_engine.pool.page_size
                or prefill_engine.scfg.max_len != decode_engine.scfg.max_len):
            raise ValueError(
                "prefill and decode engines must agree on page_size and "
                "max_len (block tables move between them verbatim)")
        self.prefill_engine = prefill_engine
        self.decode_engine = decode_engine
        self.name = name or f"disagg:{decode_engine.cfg.name}"
        self._max_pages = decode_engine._max_pages
        self.transfers = 0
        self.transfer_pages = 0
        # EDF transfer admission: sealed prefills queue here and the
        # earliest request deadline scatters first (see
        # _transfer_scatter); transfer_log records dispatch order so
        # tests can prove the reordering
        self._transfer_cv: Optional[asyncio.Condition] = None
        self._transfer_heap: List[List[Any]] = []
        self._transfer_tickets = itertools.count()
        self._transfer_busy = False
        self.transfer_log: List[Any] = []
        self._init_executors(["prefill", "decode"])

        from repro.models.attention import SCRATCH_PAGE
        self._scratch = SCRATCH_PAGE
        # fixed-width page rows keep both jits at ONE compiled shape;
        # padding rows address the scratch page on both sides, so the
        # only garbage ever copied lands where garbage already lives
        self._gather = jax.jit(
            lambda caches, pages: jax.tree.map(lambda x: x[:, pages], caches))
        self._scatter = jax.jit(
            lambda caches, pkg, dst: jax.tree.map(
                lambda c, p: c.at[:, dst].set(p), caches, pkg),
            donate_argnums=(0,))

    @classmethod
    def build(cls, cfg, params, scfg, *, num_pages: int, page_size: int = 64,
              decode_batch: int = 8, prefill_pages: Optional[int] = None,
              dtype=None, prefix_sharing: bool = True, logit_cache: int = 0,
              host_tier_pages: int = 0, spill_watermark: float = 0.0,
              name: Optional[str] = None) -> "DisaggregatedBackend":
        """Construct both engines over shared params.  ``num_pages``
        sizes the decode (serving) pool; ``prefill_pages`` the staging
        pool (defaults to the same).  Prefix sharing and the logit
        cache live on the prefill side — that is where prompts run;
        the decode pool needs neither (the transfer copy already gives
        every sequence private pages).

        ``host_tier_pages`` turns on the KV memory hierarchy on the
        *staging* pool: the gather stage's release then RETAINS a
        transferred sequence's prefix pages instead of freeing them, so
        a repeated system prompt maps them and skips the prefill
        compute entirely (the transfer still copies — the decode pool
        deliberately has no sharing), and cold retained prefixes spill
        to host RAM under pressure rather than rejecting admissions."""
        from repro.serving.engine import Engine
        pre = Engine(cfg, params, scfg)
        pre.init_paged(num_pages=prefill_pages or num_pages,
                       page_size=page_size, decode_batch=decode_batch,
                       dtype=dtype, prefix_sharing=prefix_sharing,
                       logit_cache=logit_cache,
                       host_tier_pages=host_tier_pages,
                       spill_watermark=spill_watermark)
        dec = Engine(cfg, params, scfg)
        dec.init_paged(num_pages=num_pages, page_size=page_size,
                       decode_batch=decode_batch, dtype=dtype,
                       prefix_sharing=False)
        return cls(pre, dec, name=name)

    def bind_tracer(self, tracer) -> None:
        super().bind_tracer(tracer)
        for label, engine in (("prefill", self.prefill_engine),
                              ("decode", self.decode_engine)):
            engine.tracer = tracer
            engine.trace_track = backend_track(self.name, f"{label}_engine")
            engine.pool.tracer = tracer
            engine.pool.trace_track = backend_track(self.name,
                                                    f"{label}_pool")

    # ---- token-level ---------------------------------------------------
    def begin(self, prompt, *, max_new_tokens, seed=None, temperature=None,
              stop_tokens=()):
        seq = self.prefill_engine.begin_prefill(
            prompt, max_new_tokens=max_new_tokens, seed=seed,
            temperature=temperature, stop_tokens=stop_tokens)
        seq.owner_pool = self.prefill_engine.pool
        return seq

    async def prefill_chunk(self, seq, *, chunk_tokens=None) -> bool:
        if not seq.prefill_done:
            done = await self._run(
                "prefill", lambda: self.prefill_engine.prefill_chunk(
                    seq, chunk_tokens=chunk_tokens), op="prefill_chunk")
            if not done:
                return False
        if getattr(seq, "owner_pool", None) is self.decode_engine.pool:
            return True                  # already transferred (retry path)
        tracer = self._tracer
        # the span lands on the tracer's clock; durations are the same
        clock = tracer.clock if tracer.enabled else time.monotonic
        t0 = clock()
        if getattr(seq, "transfer_package", None) is None:
            pkg, n = await self._run("prefill", self._gather_stage, seq,
                                     op="kv_gather")
            self.prefill_engine.pool.release(seq)
            seq.pages = []
            seq.owner_pool = None
            seq.transfer_package = (pkg, n)
        # OutOfPages below is backpressure: the package stays on the
        # sequence and the scheduler retries after decode frees
        dst = await self._transfer_scatter(seq)
        seq.pages = list(dst)
        seq.block_table[:] = self.decode_engine.pool.block_table(
            dst, self._max_pages)
        seq.owner_pool = self.decode_engine.pool
        seq.reclaimed_upto = 0          # fresh page list in the new pool
        seq.transfer_package = None
        self.transfers += 1
        self.transfer_pages += len(dst)
        t1 = clock()
        # transfer wait accumulates on the sequence so the scheduler
        # can attribute it to the request (carved out of prefill)
        seq.transfer_s = getattr(seq, "transfer_s", 0.0) + (t1 - t0)
        if tracer.enabled:
            tracer.span("KV_TRANSFER", backend_track(self.name, "transfer"),
                        t0, t1, {"pages": len(dst),
                                 "rid": getattr(seq, "trace_rid", None)})
        self._note_transfer(t1 - t0)
        return True

    def _gather_stage(self, seq):
        import jax
        import jax.numpy as jnp
        live = [p for p in seq.pages if p is not None]
        row = np.full((self._max_pages,), self._scratch, np.int32)
        row[:len(live)] = live
        pkg = self._gather(self.prefill_engine._paged_caches,
                           jnp.asarray(row))
        jax.block_until_ready(jax.tree.leaves(pkg)[0])
        return pkg, len(live)

    async def _transfer_scatter(self, seq):
        """Deadline-ordered (EDF) admission to the scatter stage.

        Sealed prefills used to hit the decode executor in seal order
        (FIFO), so a tight-SLO request's KV transfer could sit behind a
        batch of lax ones.  Now every transfer takes a ticket keyed by
        its request's absolute deadline (``seq.deadline_t``, inherited
        from the scheduler; direct backend users without deadlines get
        +inf and keep seal order via the ticket counter) and waits its
        turn: the earliest-deadline pending transfer dispatches next,
        one at a time.  A ticket-holder that dies (cancelled mid-wait)
        removes itself so it can never wedge the queue."""
        cv = self._transfer_cv
        if cv is None:
            cv = self._transfer_cv = asyncio.Condition()
        deadline = getattr(seq, "deadline_t", None)
        ticket = [float("inf") if deadline is None else float(deadline),
                  next(self._transfer_tickets)]
        async with cv:
            heapq.heappush(self._transfer_heap, ticket)
            try:
                await cv.wait_for(
                    lambda: (not self._transfer_busy
                             and self._transfer_heap[0] is ticket))
            except BaseException:
                self._transfer_heap.remove(ticket)
                heapq.heapify(self._transfer_heap)
                cv.notify_all()
                raise
            heapq.heappop(self._transfer_heap)
            self._transfer_busy = True
        try:
            self.transfer_log.append(getattr(seq, "trace_rid", None))
            return await self._run("decode", self._scatter_stage,
                                   seq.transfer_package, op="kv_scatter")
        finally:
            async with cv:
                self._transfer_busy = False
                cv.notify_all()

    def _scatter_stage(self, package):
        import jax
        import jax.numpy as jnp
        pkg, n = package
        dst = self.decode_engine.pool.alloc(n)       # OutOfPages: no-op
        row = np.full((self._max_pages,), self._scratch, np.int32)
        row[:n] = dst
        try:
            self.decode_engine._paged_caches = self._scatter(
                self.decode_engine._paged_caches, pkg, jnp.asarray(row))
            jax.block_until_ready(
                jax.tree.leaves(self.decode_engine._paged_caches)[0])
        except Exception:
            self.decode_engine._caches_poisoned = True
            self.decode_engine.pool.decref(dst)      # unowned: must not leak
            raise
        return dst

    async def decode_batch(self, seqs):
        return await self._run("decode",
                               self.decode_engine.decode_step_batch, seqs,
                               op="decode_step")

    @property
    def verify_engine(self):
        """Speculative verify runs against the decode engine's caches
        (that is where running sequences' K/V lives)."""
        return self.decode_engine

    def release(self, seq) -> None:
        seq.transfer_package = None
        pool = getattr(seq, "owner_pool", None)
        if pool is not None and seq.pages:
            pool.release(seq)
        seq.pages = []
        seq.owner_pool = None

    async def probe(self, prompt):
        return await self._run("prefill",
                               self.prefill_engine.prewarm_logits, prompt,
                               op="probe")

    # ---- admission -----------------------------------------------------
    def capacity(self) -> BackendCapacity:
        pool = self.decode_engine.pool
        return BackendCapacity(
            decode_batch=self.decode_engine.decode_batch,
            page_size=pool.page_size, num_pages=pool.num_pages - 1,
            free_pages=pool.num_free, cow_headroom=pool.cow_headroom,
            max_len=self.decode_engine.scfg.max_len, inflight=self._inflight)

    def admission_cost(self, prompt, max_new_tokens, *, chunk_tokens=None):
        # admission gates on the *prefill* (staging) pool: the decode
        # pool is reached only through the transfer, whose OutOfPages
        # is ordinary backpressure against decode frees
        return self.prefill_engine.admission_page_cost(
            prompt, max_new_tokens, chunk_tokens=chunk_tokens)

    def set_lazy_decode_alloc(self, enabled: bool) -> None:
        # the staging pool is where sealing reserves pages (the decode
        # pool always grows transferred sequences page-by-page)
        self.prefill_engine.set_lazy_decode_alloc(enabled)

    def admissible(self, prompt, max_new_tokens, *, chunk_tokens=None):
        need, extra = self.admission_cost(prompt, max_new_tokens,
                                          chunk_tokens=chunk_tokens)
        pool = self.prefill_engine.pool

        def free():      # cold retained prefixes spill instead of rejecting
            return pool.num_free + pool.reclaimable_pages
        ok = need + pool.cow_headroom + extra <= free()
        if not ok and self.prefill_engine.shed_prewarmed():
            ok = need + pool.cow_headroom + extra <= free()
        return ok

    def fits_ever(self, prompt_len, max_new_tokens):
        need = self.decode_engine.pool.pages_for(prompt_len + max_new_tokens)
        return (need <= self.decode_engine.pool.num_pages - 1
                and need <= self.prefill_engine.pool.num_pages - 1)

    @property
    def healthy(self) -> bool:
        return not (self.prefill_engine.caches_poisoned
                    or self.decode_engine.caches_poisoned)

    # ---- warmup / reporting -------------------------------------------
    def warmup(self, prompt_lens, chunk_tokens=None):
        """Compile prefill shapes on the prefill engine, then run one
        tiny sequence through the full begin -> chunk -> transfer ->
        decode pipeline synchronously so the gather/scatter jits and
        the decode step compile before traffic."""
        _engine_warmup(self.prefill_engine, prompt_lens, chunk_tokens)
        try:
            seq = self.begin(np.zeros((1,), np.int32), max_new_tokens=2)
            try:
                while not self.prefill_engine.prefill_chunk(
                        seq, chunk_tokens=chunk_tokens):
                    pass
                seq.transfer_package = self._gather_stage(seq)
                self.prefill_engine.pool.release(seq)
                seq.pages, seq.owner_pool = [], None
                dst = self._scatter_stage(seq.transfer_package)
                seq.pages = list(dst)
                seq.block_table[:] = self.decode_engine.pool.block_table(
                    dst, self._max_pages)
                seq.owner_pool = self.decode_engine.pool
                seq.transfer_package = None
                self.decode_engine.decode_step_batch([seq])
            finally:
                self.release(seq)
        except OutOfPages:
            pass                        # pool too small: first use compiles

    def prefix_digest(self, cap: int = 2048) -> List[str]:
        # the staging pool is where sharing and the host tier live —
        # that is the coverage a routed repeat prompt would hit
        return self.prefill_engine.pool.chunk_digest(cap)

    def stats(self) -> Dict[str, Any]:
        pre, dec = self.prefill_engine, self.decode_engine
        return {
            "name": self.name, "healthy": self.healthy,
            "pool": dec.pool.stats(),
            "prefill_pool": pre.pool.stats(),
            "prefill_tokens_computed": pre.prefill_tokens_computed,
            "prefill_tokens_shared": pre.prefill_tokens_shared,
            "cow_copies": pre.cow_count + dec.cow_count,
            "reclaimed_pages": pre.reclaimed_pages + dec.reclaimed_pages,
            "logit_cache_hits": pre.logit_cache_hits,
            "logit_cache_misses": pre.logit_cache_misses,
            "transfers": self.transfers,
            "transfer_pages": self.transfer_pages,
        }


# ===========================================================================
# Remote stub: wire schema over an in-process duplex channel
# ===========================================================================

WIRE_VERSION = 2
#: versions this build speaks.  v2 added hello version negotiation,
#: acked ``release`` replies (the retry loop that makes a lost release
#: frame leak-free), the ``status`` op (capacity + queue depth +
#: prefix-digest gossip for the cluster router), deadline inheritance
#: on begin payloads, and the socket transport's streaming decode push
#: frames.  v1 (request/response only, fire-and-forget release) is
#: retired: a v1 peer is rejected at hello, in both directions.
WIRE_VERSIONS: Tuple[int, ...] = (2,)


class WireVersionError(RuntimeError):
    """hello negotiation found no common wire version."""


def negotiate_wire_version(peer_versions: Sequence[int]) -> int:
    """Highest version both sides speak; raises WireVersionError when
    the intersection is empty (the reply crosses the wire, so the
    rejected peer learns exactly what this build speaks)."""
    common = {int(v) for v in peer_versions} & set(WIRE_VERSIONS)
    if not common:
        raise WireVersionError(
            f"wire version mismatch: peer speaks "
            f"{sorted(int(v) for v in peer_versions)}, this build speaks "
            f"{sorted(WIRE_VERSIONS)}")
    return max(common)


#: wire error type -> exception class raised client-side
_WIRE_ERRORS = {
    "OutOfPages": OutOfPages,
    "ValueError": ValueError,
    "RuntimeError": RuntimeError,
    "WireVersionError": WireVersionError,
    "BackendLost": BackendLost,
}

#: request-local victim tags the engine pins on OutOfPages; each must
#: cross the wire, because the scheduler's recovery path fails ONLY
#: the tagged sequence — an OutOfPages that arrives without its victim
#: is treated as a backend death and kills every request on the worker
_VICTIM_TAGS = (("cow_seq", "cow_sid"), ("grow_seq", "grow_sid"))


def wire_error_payload(exc: BaseException,
                       seqs: Dict[int, Any]) -> Dict[str, Any]:
    """Serialize an exception for an ``err`` reply/push, resolving any
    victim-sequence tags (``cow_seq``/``grow_seq``) to sids through the
    server's sequence table so the client can re-attach them."""
    err: Dict[str, Any] = {"type": type(exc).__name__, "msg": str(exc)}
    for attr, key in _VICTIM_TAGS:
        victim = getattr(exc, attr, None)
        if victim is not None:
            err[key] = next(
                (sid for sid, s in seqs.items() if s is victim), None)
    return err


def wire_error_rehydrate(err: Dict[str, Any],
                         mirrors: Dict[int, Any]) -> BaseException:
    """Inverse of :func:`wire_error_payload`: a typed exception with
    victim sids resolved back to this client's mirror sequences."""
    exc = _WIRE_ERRORS.get(err["type"], RuntimeError)(err["msg"])
    for attr, key in _VICTIM_TAGS:
        sid = err.get(key)
        if sid is not None:
            victim = mirrors.get(sid)
            if victim is not None:
                setattr(exc, attr, victim)
    return exc


def wire_encode(msg: Dict[str, Any]) -> str:
    """Serialize one message.  Everything on the wire is JSON — the
    assertion that no live object crosses the seam."""
    def default(o):
        if isinstance(o, np.integer):
            return int(o)
        if isinstance(o, np.floating):
            return float(o)
        if isinstance(o, np.ndarray):
            return o.tolist()
        raise TypeError(f"not wire-serializable: {type(o)!r}")
    return json.dumps(msg, default=default)


def wire_decode(raw: str) -> Dict[str, Any]:
    return json.loads(raw)


class DuplexChannel:
    """In-process stand-in for a bidirectional RPC transport: two
    queues of wire-encoded strings.  A real deployment replaces this
    with a socket/mesh transport; nothing else changes."""

    def __init__(self):
        self.to_server: asyncio.Queue = asyncio.Queue()
        self.to_client: asyncio.Queue = asyncio.Queue()


@dataclasses.dataclass
class RemoteSequence:
    """Client-side mirror of one remote sequence — exactly the fields
    the token-level scheduler reads, kept in sync from responses."""
    sid: int
    prompt: np.ndarray
    prompt_len: int
    max_new_tokens: int
    seed: Optional[int]
    temperature: Optional[float]
    stop_tokens: Tuple[int, ...]
    prefill_pos: int = 0
    shared_prefix_len: int = 0
    prefill_done: bool = False
    tokens: List[int] = dataclasses.field(default_factory=list)
    pos: int = 0
    done: bool = False
    finish_reason: str = "length"
    begun: bool = False                  # server-side twin exists
    pages: List[int] = dataclasses.field(default_factory=list)  # unused

    def apply(self, state: Dict[str, Any]) -> None:
        for k in ("prefill_pos", "shared_prefix_len", "prefill_done",
                  "pos", "done", "finish_reason"):
            if k in state:
                setattr(self, k, state[k])
        if "tokens" in state:
            self.tokens = [int(t) for t in state["tokens"]]
        if "new_tokens" in state:
            # one decode call may append SEVERAL tokens (speculative
            # decoding commits draft runs); takes precedence over the
            # legacy single-token key, never both
            self.tokens.extend(int(t) for t in state["new_tokens"])
        elif "new_token" in state:
            self.tokens.append(int(state["new_token"]))


class BackendServer:
    """Server half of the stub: drives any inner ``ModelBackend`` from
    wire messages.  One request at a time, in arrival order — the
    stub trades concurrency for a dead-simple protocol; the disagg
    backend is where concurrency lives.

    The op dispatcher is transport-agnostic: the in-process stub runs
    ``serve()`` over a ``DuplexChannel``, while the cluster socket
    transport (``repro.serving.cluster.transport``) instantiates a
    channel-less ``BackendServer`` per client session and calls
    ``_dispatch`` on frames it reads off the socket."""

    def __init__(self, inner: ModelBackend,
                 channel: Optional[DuplexChannel] = None):
        self.inner = inner
        self.channel = channel
        self._seqs: Dict[int, Any] = {}

    def _state_of(self, seq, *, tokens: bool = False) -> Dict[str, Any]:
        st = {"prefill_pos": int(seq.prefill_pos),
              "shared_prefix_len": int(seq.shared_prefix_len),
              "prefill_done": bool(seq.prefill_done),
              "pos": int(seq.pos), "done": bool(seq.done),
              "finish_reason": str(seq.finish_reason)}
        if tokens:
            st["tokens"] = [int(t) for t in seq.tokens]
        return st

    def reclaim(self) -> int:
        """Release every sequence this session holds (shutdown /
        orphaned-session cleanup).  Returns how many were reclaimed."""
        n = len(self._seqs)
        for seq in self._seqs.values():
            self.inner.release(seq)
        self._seqs.clear()
        return n

    async def serve(self) -> None:
        while True:
            msg = wire_decode(await self.channel.to_server.get())
            if msg["op"] == "shutdown":
                self.reclaim()                  # disconnect reclaims
                self._reply(msg, {})
                return
            try:
                self._reply(msg, await self._dispatch(msg))
            except Exception as exc:            # noqa: BLE001 — wire it
                self._reply(msg, None,
                            err=wire_error_payload(exc, self._seqs))

    def _reply(self, msg, ok, err=None) -> None:
        reply = {"v": WIRE_VERSION, "id": msg["id"],
                 "healthy": self.inner.healthy,
                 "cap": dataclasses.asdict(self.inner.capacity())}
        if err is None:
            reply["ok"] = ok
        else:
            reply["err"] = err
        self.channel.to_client.put_nowait(wire_encode(reply))

    async def _dispatch(self, msg) -> Dict[str, Any]:
        op, body = msg["op"], msg.get("body", {})
        if op == "hello":
            # negotiation: the peer states every version it speaks
            # (legacy v1 hellos carry no list — their envelope "v" is
            # the whole claim); no overlap is a typed rejection that
            # tells the peer what this build speaks
            v = negotiate_wire_version(
                body.get("versions") or [msg.get("v", 1)])
            cap = self.inner.capacity()
            return {"v": v, "versions": list(WIRE_VERSIONS),
                    "page_size": cap.page_size,
                    "num_pages": cap.num_pages,
                    "decode_batch": cap.decode_batch,
                    "max_len": cap.max_len}
        if op == "prefill_chunk":
            sid = body["sid"]
            seq = self._seqs.get(sid)
            if seq is None:
                b = body.get("begin")
                if b is None:
                    raise ValueError(f"unknown sequence {sid} and no begin "
                                     f"payload (released, or begin failed)")
                seq = self.inner.begin(
                    np.asarray(b["prompt"], np.int32),
                    max_new_tokens=b["max_new_tokens"], seed=b["seed"],
                    temperature=b["temperature"],
                    stop_tokens=tuple(b["stop_tokens"]))
                if b.get("deadline_rel") is not None:
                    # deadline inheritance: the client ships seconds-to-
                    # deadline (clocks differ across hosts); the server
                    # re-anchors it so an inner disaggregated backend's
                    # EDF transfer queue orders by the real SLO
                    seq.deadline_t = time.monotonic() + b["deadline_rel"]
                self._seqs[sid] = seq
            done = await self.inner.prefill_chunk(
                seq, chunk_tokens=body["chunk_tokens"])
            return {"done": bool(done),
                    "state": self._state_of(seq, tokens=done)}
        if op == "decode":
            seqs = [self._seqs[sid] for sid in body["sids"]]
            # snapshot per-row token counts first: a speculative inner
            # backend commits a RUN of tokens per call, and the client
            # mirror needs every one of them
            before = [len(s.tokens) for s in seqs]
            await self.inner.decode_batch(seqs)
            return {"rows": [dict(self._state_of(s),
                                  sid=sid,
                                  new_tokens=[int(t)
                                              for t in s.tokens[n0:]])
                             for sid, s, n0 in zip(body["sids"], seqs,
                                                   before)]}
        if op == "release":
            # acked and idempotent: the client retries until it sees
            # this reply, and releasing an unknown sid (already
            # reclaimed, or a retry of a release that DID land) is a
            # clean no-op — that pairing is what makes a release frame
            # lost to a reconnect leak-free
            seq = self._seqs.pop(body["sid"], None)
            if seq is not None:
                self.inner.release(seq)
            return {"released": seq is not None}
        if op == "status":
            # the cluster heartbeat: capacity rides the reply envelope;
            # the body gossips load, the prefix-chunk digest the router
            # scores placement against, and the prefill-work counters
            # the router reports per host
            st = self.inner.stats()
            return {"queue_depth": self.inner.capacity().inflight,
                    "seqs": len(self._seqs),
                    "digest": self.inner.prefix_digest(
                        int(body.get("digest_cap", 2048))),
                    "prefill_tokens_computed":
                        st.get("prefill_tokens_computed", 0),
                    "prefill_tokens_shared":
                        st.get("prefill_tokens_shared", 0)}
        raise ValueError(f"unknown wire op {op!r}")


class RemoteStubBackend(ModelBackend):
    """Client half of the stub: the scheduler-facing backend whose
    every data-plane call crosses ``DuplexChannel`` as JSON.

    The mirror sequences it hands the scheduler are updated purely
    from wire responses — nothing on this side touches the pool — so
    swapping the channel for a real transport (and the server for a
    per-slice process) is a transport change, not a scheduler change.
    Admission is conservative: the client budgets the full page span
    from the handshake geometry (no sharing discount); a stale free
    count simply surfaces as OutOfPages backpressure, which the
    scheduler already retries.  ``warmup`` and ``stats`` are
    control-plane and proxy the inner backend directly."""

    def __init__(self, inner: ModelBackend, name: Optional[str] = None):
        self.inner = inner
        self.name = name or f"remote:{inner.name}"
        self.channel = DuplexChannel()
        self._server = BackendServer(inner, self.channel)
        self._server_task: Optional[asyncio.Task] = None
        self._reader_task: Optional[asyncio.Task] = None
        self._pending: Dict[int, asyncio.Future] = {}
        self._ids = itertools.count()
        self._sids = itertools.count()
        self._mirrors: Dict[int, RemoteSequence] = {}
        self._cap = inner.capacity()        # refreshed from every reply
        self._healthy = True
        self._geom: Dict[str, int] = {}
        self.messages_sent = 0
        # releases awaiting their server ack; each retries until acked
        # (idempotent server-side), so none can leak server pages
        self._pending_releases: set = set()
        self._release_tasks: set = set()

    # ---- lifecycle -----------------------------------------------------
    async def start(self) -> None:
        await self.inner.start()
        self._server_task = asyncio.ensure_future(self._server.serve())
        self._reader_task = asyncio.ensure_future(self._read_loop())
        self._geom = await self._call(
            "hello", {"versions": list(WIRE_VERSIONS)})
        if self._geom["v"] not in WIRE_VERSIONS:
            raise WireVersionError(
                f"wire version mismatch: server negotiated "
                f"{self._geom['v']}, this client speaks "
                f"{sorted(WIRE_VERSIONS)}")

    async def stop(self) -> None:
        if self._server_task is not None:
            # let in-flight release acks land first: shutdown reclaims
            # leftovers anyway, but an abandoned release task would die
            # noisily with the loop
            while self._release_tasks:
                await asyncio.gather(*list(self._release_tasks),
                                     return_exceptions=True)
            try:
                await self._call("shutdown")
            finally:
                await self._server_task
                self._server_task = None
                if self._reader_task is not None:
                    self._reader_task.cancel()
                    try:
                        await self._reader_task
                    except asyncio.CancelledError:
                        pass
                    self._reader_task = None
        await self.inner.stop()

    async def _read_loop(self) -> None:
        while True:
            msg = wire_decode(await self.channel.to_client.get())
            self._healthy = bool(msg.get("healthy", True))
            if "cap" in msg:
                self._cap = BackendCapacity(**msg["cap"])
            fut = self._pending.pop(msg["id"], None)
            if fut is not None and not fut.done():
                fut.set_result(msg)     # fire-and-forget replies drop here

    def bind_tracer(self, tracer) -> None:
        # control-plane: the inner backend serves the device work, so
        # its executor/engine/pool instrumentation must see the tracer
        # too; this side traces the wire round-trips
        super().bind_tracer(tracer)
        self.inner.bind_tracer(tracer)

    async def _call(self, op: str, body: Optional[Dict] = None
                    ) -> Dict[str, Any]:
        if self._server_task is None:
            raise RuntimeError(
                f"backend {self.name!r} is not started: no channel")
        mid = next(self._ids)
        fut = asyncio.get_running_loop().create_future()
        self._pending[mid] = fut
        self.messages_sent += 1
        tracer = self._tracer
        t0 = tracer.clock() if tracer.enabled else 0.0
        self.channel.to_server.put_nowait(
            wire_encode({"v": WIRE_VERSION, "id": mid, "op": op,
                         "body": body or {}}))
        msg = await fut
        if tracer.enabled:
            tracer.span(op, backend_track(self.name, "wire"), t0,
                        tracer.clock(), {"mid": mid})
        if "err" in msg:
            raise wire_error_rehydrate(msg["err"], self._mirrors)
        return msg["ok"]

    # ---- token-level ---------------------------------------------------
    def begin(self, prompt, *, max_new_tokens, seed=None, temperature=None,
              stop_tokens=()):
        prompt_np = np.asarray(prompt, np.int32).reshape((-1,))
        p = len(prompt_np)
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1 (prefill always samples the "
                f"first token), got {max_new_tokens}")
        if p < 1:
            raise ValueError("prompt must hold at least one token")
        max_len = self._geom.get("max_len") or self._cap.max_len
        if max_len and p + max_new_tokens > max_len:
            raise ValueError(
                f"prompt length {p} + max_new_tokens {max_new_tokens} "
                f"exceeds the remote engine's cache capacity "
                f"max_len={max_len}")
        seq = RemoteSequence(
            sid=next(self._sids), prompt=prompt_np, prompt_len=p,
            max_new_tokens=max_new_tokens, seed=seed, temperature=temperature,
            stop_tokens=tuple(int(t) for t in stop_tokens))
        self._mirrors[seq.sid] = seq
        return seq

    async def prefill_chunk(self, seq, *, chunk_tokens=None) -> bool:
        body: Dict[str, Any] = {"sid": seq.sid, "chunk_tokens": chunk_tokens}
        if not seq.begun:
            deadline_t = getattr(seq, "deadline_t", None)
            body["begin"] = {"prompt": seq.prompt.tolist(),
                             "max_new_tokens": seq.max_new_tokens,
                             "seed": seq.seed,
                             "temperature": seq.temperature,
                             "stop_tokens": list(seq.stop_tokens),
                             # seconds-to-deadline, not absolute: the
                             # server re-anchors on its own clock
                             "deadline_rel": (
                                 None if deadline_t is None
                                 else max(0.0,
                                          deadline_t - time.monotonic()))}
            # mark begun BEFORE awaiting: an error reply (e.g.
            # OutOfPages backpressure) may leave the server-side twin
            # registered and holding shared-prefix increfs, so the
            # later release() must send the release op regardless —
            # the server drops unknown sids leniently
            seq.begun = True
        ok = await self._call("prefill_chunk", body)
        seq.apply(ok["state"])
        return ok["done"]

    async def decode_batch(self, seqs):
        ok = await self._call("decode", {"sids": [s.sid for s in seqs]})
        out = []
        for seq, row in zip(seqs, ok["rows"]):
            seq.apply(row)
            out.append(seq.tokens[-1])
        return np.asarray(out, np.int32)

    def release(self, seq) -> None:
        self._mirrors.pop(seq.sid, None)
        if self._server_task is None or not seq.begun:
            return              # never reached the server / it reclaimed
        seq.begun = False
        # acked-with-retry (v2): the sync protocol surface spawns a
        # task that awaits the server's {"released": ...} reply and
        # retries until it sees one — a release is only forgotten once
        # the server confirmed it (or shutdown reclaimed everything)
        self._pending_releases.add(seq.sid)
        task = asyncio.ensure_future(self._release_with_retry(seq.sid))
        self._release_tasks.add(task)
        task.add_done_callback(self._release_tasks.discard)

    async def _release_with_retry(self, sid: int) -> None:
        # retried until acked — never a fixed attempt budget: giving up
        # while the server lives would silently leak its pages.  The
        # only exit without an ack is the server going away entirely
        # (shutdown reclaim owns the leftovers); the sid then STAYS in
        # _pending_releases so stats expose what was never confirmed.
        backoff = 0.05
        while self._server_task is not None and not self._server_task.done():
            try:
                await self._call("release", {"sid": sid})
            except asyncio.CancelledError:
                raise
            except Exception:   # noqa: BLE001 — transport hiccup: retry
                await asyncio.sleep(backoff)
                backoff = min(backoff * 2, 1.0)
                continue
            self._pending_releases.discard(sid)
            return

    # ---- admission (conservative, from the cached wire snapshot) -------
    def capacity(self) -> BackendCapacity:
        return self._cap

    @property
    def healthy(self) -> bool:
        return self._healthy

    # ---- control plane -------------------------------------------------
    def warmup(self, prompt_lens, chunk_tokens=None):
        self.inner.warmup(prompt_lens, chunk_tokens)

    def stats(self) -> Dict[str, Any]:
        s = dict(self.inner.stats())
        s.update({"name": self.name, "wire_messages": self.messages_sent,
                  "pending_releases": len(self._pending_releases)})
        return s
