"""Batched serving engine: prefill + decode.

One engine serves one model.  The multiplexed front-end (the paper's
contribution) lives in repro.serving.mux_server and composes N engines.

Two cache disciplines:
  * ``generate`` — the classic fixed-shape path: one ring-buffer KV
    slab per batch slot, every request in the batch at the same
    position.  Memory = max_len x batch regardless of actual lengths.
  * ``init_paged`` + ``prefill_into_pages`` / ``decode_step_batch`` —
    the paged path: KV lives in a pool of (page_size)-token pages
    shared by all in-flight requests (repro.serving.kv_cache.PagePool),
    each request holds ceil(tokens/page_size) pages addressed through a
    block-table row, and a decode batch mixes requests at *different*
    positions (per-row pos vector).  This is what the token-level
    continuous-batching scheduler drives: requests prefill into free
    pages, join the running decode batch, and free their pages the
    step they finish.
"""
from __future__ import annotations

import collections
import dataclasses
import hashlib
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import transformer as tf
from repro.models.attention import SCRATCH_PAGE
from repro.serving.kv_cache import OutOfPages, PagePool, PagedSequence
from repro.serving.observability.tracer import NULL_TRACER
from repro.sharding.partition import axis_rules


@dataclasses.dataclass
class ServeConfig:
    max_len: int = 256                  # cache capacity per request
    temperature: float = 0.0            # 0 = greedy
    seed: int = 0


class Engine:
    """jit-compiled prefill/decode for a fixed batch shape."""

    def __init__(self, cfg: ModelConfig, params: Any, scfg: ServeConfig,
                 rules=None):
        self.cfg = cfg
        self.scfg = scfg
        self.params = params
        self.rules = rules

        def prefill_fn(p, tokens, image_embeds):
            return tf.prefill(p, cfg, tokens, image_embeds=image_embeds,
                              cache_len=scfg.max_len)

        def decode_fn(p, token, caches, pos):
            return tf.decode_step(p, cfg, token, caches, pos)

        ctx = axis_rules(rules) if rules is not None else None
        if ctx:
            with ctx:
                self._prefill = jax.jit(prefill_fn)
                self._decode = jax.jit(decode_fn, donate_argnums=(2,))
        else:
            self._prefill = jax.jit(prefill_fn)
            self._decode = jax.jit(decode_fn, donate_argnums=(2,))

        # serializes the donating paged entry points (prefill_chunk /
        # decode_step_batch): both reassign self._paged_caches through
        # donating jits, so two threads — e.g. a backend executor and
        # a MuxServer.probe prewarm on the caller thread — must never
        # overlap on one engine.  RLock: prefill_into_pages loops
        # prefill_chunk under one acquisition per chunk.
        self._device_lock = threading.RLock()
        # paged state (populated by init_paged)
        self.pool: Optional[PagePool] = None
        self._paged_caches = None
        self._paged_prefill = None
        self._paged_prefill_tail = None
        self._paged_decode = None
        self._paged_decode_cow = None
        self._paged_verify = None
        self._lazy_decode_alloc = False
        self._max_pages = 0
        self._decode_batch = 0
        self._caches_poisoned = False
        # prefix-sharing accounting (the benchmark's evidence): prompt
        # tokens actually run through prefill (padded) vs mapped from a
        # resident shared prefix, and copy-on-write page copies made
        self.prefill_tokens_computed = 0
        self.prefill_tokens_shared = 0
        self.cow_count = 0
        # cross-request logit cache: full-prompt chain hash -> the
        # final prompt token's logits row.  A fully-resident repeat
        # prompt (every page mapped from the prefix index) skips even
        # the one-token tail prefill — a zero-FLOP admission.  Bounded
        # LRU; disabled at capacity 0.
        self._logit_cache: "collections.OrderedDict[bytes, np.ndarray]" = \
            collections.OrderedDict()
        self._logit_cache_cap = 0
        self.logit_cache_hits = 0
        self.logit_cache_misses = 0
        # probe-path prewarm residents (prompt key -> held sequence):
        # the mux probe keeps a scored prompt's pages mapped so the
        # follow-up admission is a zero-FLOP logit-cache hit
        self._prewarmed: "collections.OrderedDict[bytes, PagedSequence]" = \
            collections.OrderedDict()
        self._prewarm_cap = 0
        # window/chunked span reclaim (None = a full-span layer exists)
        self._layer_spans: Optional[List[Tuple[str, int]]] = None
        self._span_reclaim = True
        self.reclaimed_pages = 0
        # tracing: COW / span-reclaim / logit-cache-hit / prewarm
        # instants record here when a backend binds a live tracer
        # (bind_tracer sets both attrs); the null default costs nothing
        self.tracer = NULL_TRACER
        self.trace_track = f"engine:{cfg.name}/events"

    @property
    def caches_poisoned(self) -> bool:
        """True once a paged jit call failed at execution time: both
        paged entry points donate the cache buffers, so such a failure
        deletes them and the engine cannot serve the paged path again
        (rebuild via init_paged).  The scheduler uses this to tell a
        request-local error from a dead engine."""
        return self._caches_poisoned

    def _check_capacity(self, p: int, max_new_tokens: int) -> None:
        if p + max_new_tokens > self.scfg.max_len:
            raise ValueError(
                f"prompt length {p} + max_new_tokens {max_new_tokens} "
                f"exceeds the engine's cache capacity "
                f"max_len={self.scfg.max_len}; raise ServeConfig.max_len "
                f"or shorten the request")

    def _sample(self, logits, key):
        if self.scfg.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            key, logits / self.scfg.temperature, axis=-1).astype(jnp.int32)

    def _sample_rows(self, logits, seeds, positions, temps=None):
        """Per-row sampling for the paged batch: row i's key is
        fold_in(key(seeds[i]), positions[i]), so a request's sampled
        tokens do not depend on which other requests share its batch.
        ``temps`` carries per-request temperature overrides (None entry
        = engine default); rows at temperature <= 0 take the argmax."""
        if temps is None:
            t = np.full((np.shape(logits)[0],), self.scfg.temperature,
                        np.float32)
        else:
            t = np.asarray([self.scfg.temperature if x is None else x
                            for x in temps], np.float32)
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if not (t > 0.0).any():
            return greedy
        keys = jax.vmap(lambda s, p: jax.random.fold_in(jax.random.key(s), p)
                        )(jnp.asarray(seeds, jnp.uint32),
                          jnp.asarray(positions, jnp.int32))
        safe_t = jnp.where(t > 0.0, t, 1.0)
        sampled = jax.vmap(lambda k, l: jax.random.categorical(k, l))(
            keys, logits / safe_t[:, None]).astype(jnp.int32)
        return jnp.where(jnp.asarray(t) > 0.0, sampled, greedy)

    def generate(self, prompts: jnp.ndarray, *, max_new_tokens: int,
                 image_embeds: Optional[jnp.ndarray] = None) -> Dict[str, Any]:
        """prompts: (B, P) int32 (or (B, P, K) multi-codebook).

        Returns {tokens (B, P+N), prefill_s, decode_s, tokens_per_s}.
        """
        b, p = prompts.shape[:2]
        self._check_capacity(p, max_new_tokens)
        key = jax.random.key(self.scfg.seed)
        t0 = time.time()
        logits, caches = self._prefill(self.params, prompts, image_embeds)
        tok = self._sample(logits[:, 0], key)      # (B,) or (B, K)
        jax.block_until_ready(tok)
        t1 = time.time()
        out = [prompts, tok.reshape((b, 1) + prompts.shape[2:])]
        for i in range(max_new_tokens - 1):
            key = jax.random.fold_in(key, i)
            logits, caches = self._decode(self.params, out[-1], caches, p + i)
            nxt = self._sample(logits[:, 0], key)
            out.append(nxt.reshape((b, 1) + prompts.shape[2:]))
        jax.block_until_ready(out[-1])
        t2 = time.time()
        tokens = jnp.concatenate(out, axis=1)
        return {"tokens": tokens, "prefill_s": t1 - t0, "decode_s": t2 - t1,
                "tokens_per_s": b * max_new_tokens / max(t2 - t1, 1e-9)}

    # ------------------------------------------------------------------
    # Paged path: pool-backed caches, token-level continuous decode
    # ------------------------------------------------------------------
    def init_paged(self, *, num_pages: int, page_size: int = 64,
                   decode_batch: int = 8, dtype=None,
                   prefix_sharing: bool = True,
                   logit_cache: int = 0,
                   span_reclaim: bool = True,
                   lazy_decode_alloc: bool = False,
                   host_tier_pages: int = 0,
                   spill_watermark: float = 0.0) -> PagePool:
        """Allocate the paged KV pool and compile the paged entry
        points.  ``dtype=None`` honors ``cfg.kv_cache_dtype`` (int8
        pools store quantized pages, dequantized in-kernel).  The pool
        is sized in *pages*, not batch slots: memory scales with
        resident tokens, not max_len x batch.  ``prefix_sharing=False``
        disables the prefix index (every request prefills and holds
        private pages — the pre-sharing baseline).  ``logit_cache`` is
        the LRU capacity of the cross-request logit cache (0 = off): a
        repeat prompt whose pages are all still resident skips even the
        final-token tail prefill and samples from the cached logits.
        ``span_reclaim=False`` disables decode-time freeing of pages
        that have fallen wholly below every layer's attention span (the
        window/chunked memory reclaim; a no-op anyway when any layer
        attends the full context).  ``lazy_decode_alloc=True`` seals a
        prefill with only the prompt's pages instead of reserving the
        whole prompt+budget span — decode steps then grow the sequence
        page-by-page as it advances.  The speculative drafter runs its
        engine this way so a rejected draft's pages can be handed back
        (``rollback_pages``) instead of sitting reserved.

        ``host_tier_pages > 0`` turns on the KV memory hierarchy
        (repro.serving.kv_host_tier): the pool becomes a
        ``TieredPagePool`` that retains finished sequences' prefix
        pages, spills them to a host-RAM tier under pressure (or
        proactively past ``spill_watermark``, a fraction of allocatable
        pages to keep free), and restores them through a fixed-shape
        gather/scatter transfer on a later prefix hit — a host hit
        prefills only the divergent tail."""
        if self.cfg.num_codebooks:
            raise NotImplementedError(
                "paged decode supports single-stream token LMs")
        self.host_tier = None
        if host_tier_pages > 0:
            from repro.serving.kv_host_tier import HostTier, TieredPagePool
            self.host_tier = HostTier(host_tier_pages, page_size=page_size)
            self.pool = TieredPagePool(num_pages=num_pages,
                                       page_size=page_size,
                                       prefix_sharing=prefix_sharing,
                                       host_tier=self.host_tier,
                                       spill_watermark=spill_watermark)
        else:
            self.pool = PagePool(num_pages=num_pages, page_size=page_size,
                                 prefix_sharing=prefix_sharing)
        self._max_pages = self.pool.pages_for(self.scfg.max_len)
        if self.host_tier is not None:
            self.pool.bind_spill(self._spill_pages, self._max_pages)
        self._decode_batch = decode_batch
        self._caches_poisoned = False
        self.prefill_tokens_computed = 0
        self.prefill_tokens_shared = 0
        self.cow_count = 0
        self._logit_cache = collections.OrderedDict()
        self._logit_cache_cap = int(logit_cache)
        self.logit_cache_hits = 0
        self.logit_cache_misses = 0
        self._prewarmed = collections.OrderedDict()
        self._prewarm_cap = max(1, min(4, int(logit_cache)))
        self._span_reclaim = span_reclaim
        self._layer_spans = self._banded_spans()
        self.reclaimed_pages = 0
        self._lazy_decode_alloc = lazy_decode_alloc
        cfg = self.cfg
        self._paged_caches = tf.init_caches(cfg, 0, 0, dtype,
                                            num_pages=num_pages,
                                            page_size=page_size)

        def paged_prefill_fn(p, tokens, caches, bt, last_index):
            return tf.prefill_paged(p, cfg, tokens, caches, bt, last_index)

        def paged_prefill_tail_fn(p, tokens, caches, bt, last_index,
                                  q_offset, insert_from):
            return tf.prefill_paged(p, cfg, tokens, caches, bt, last_index,
                                    q_offset=q_offset,
                                    insert_from=insert_from)

        def paged_decode_fn(p, token, caches, bt, pos):
            return tf.decode_step(p, cfg, token, caches, pos,
                                  block_tables=bt)

        def paged_verify_fn(p, tokens, caches, bt, q_offset):
            # speculative verify: S = k+1 tokens per row at per-row
            # absolute positions, logits for every fed position
            return tf.verify_paged(p, cfg, tokens, caches, bt, q_offset)

        def paged_decode_cow_fn(p, token, caches, bt, pos, src, dst):
            # fused copy-on-write: duplicate the shared pages into this
            # step's private copies (leaves are (G, num_pages, ps, ...);
            # src/dst are (decode_batch,) page ids, scratch->scratch for
            # rows that don't COW) and run the decode insert on the
            # copied caches — one launched program, no standalone copy
            # kernel before the step
            caches = jax.tree.map(lambda x: x.at[:, dst].set(x[:, src]),
                                  caches)
            return tf.decode_step(p, cfg, token, caches, pos,
                                  block_tables=bt)

        def tier_gather_fn(caches, pages):
            # host-tier spill: pull whole pages off the device.  NOT
            # donating — the pages stay valid until the pool decrefs
            # them after the host store commits.
            return jax.tree.map(lambda x: x[:, pages], caches)

        def tier_scatter_fn(caches, package, pages):
            # host-tier restore: land host pages in freshly-allocated
            # device pages (rows padded with the scratch page id, so
            # zero-pad garbage goes where garbage already lives)
            return jax.tree.map(lambda c, pkg: c.at[:, pages].set(pkg),
                                caches, package)

        def compile_all():
            self._paged_prefill = jax.jit(paged_prefill_fn,
                                          donate_argnums=(2,))
            self._paged_prefill_tail = jax.jit(paged_prefill_tail_fn,
                                               donate_argnums=(2,))
            self._paged_decode = jax.jit(paged_decode_fn, donate_argnums=(2,))
            self._paged_decode_cow = jax.jit(paged_decode_cow_fn,
                                             donate_argnums=(2,))
            self._paged_verify = jax.jit(paged_verify_fn, donate_argnums=(2,))
            self._tier_gather = jax.jit(tier_gather_fn)
            self._tier_scatter = jax.jit(tier_scatter_fn,
                                         donate_argnums=(0,))

        ctx = axis_rules(self.rules) if self.rules is not None else None
        if ctx:
            with ctx:
                compile_all()
        else:
            compile_all()
        if self.host_tier is not None:
            # pre-compile the tier transfer on scratch-only page lists
            # (gather scratch, scatter it straight back): the first real
            # spill/restore must not pay a mid-serve XLA compile
            idle = jnp.full((self._max_pages,), SCRATCH_PAGE, jnp.int32)
            pkg = self._tier_gather(self._paged_caches, idle)
            self._paged_caches = self._tier_scatter(self._paged_caches,
                                                    pkg, idle)
            jax.block_until_ready(jax.tree.leaves(self._paged_caches)[0])
        return self.pool

    @property
    def decode_batch(self) -> int:
        """Decode-batch capacity of the paged path (0 before
        init_paged) — part of the engine's paged-serving contract."""
        return self._decode_batch

    def devices(self) -> set:
        """Devices holding this engine's parameters and paged pool."""
        leaves = jax.tree.leaves((self.params, self._paged_caches))
        return {d for x in leaves for d in x.devices()}

    def lower_paged_decode(self):
        """The paged decode step lowered at this engine's serving shapes
        (its pool, ``decode_batch`` rows, the block-table width);
        ``.compile().as_text()`` is the program every decode step runs."""
        if self.pool is None:
            raise RuntimeError("no paged KV pool: call init_paged() first")
        cap = self._decode_batch
        return self._paged_decode.lower(
            self.params, jnp.zeros((cap, 1), jnp.int32), self._paged_caches,
            jnp.zeros((cap, self._max_pages), jnp.int32),
            jnp.zeros((cap,), jnp.int32))

    # ---- window/chunked span reclaim ----------------------------------
    def _banded_spans(self) -> Optional[List[Tuple[str, int]]]:
        """(kind, span) per pattern layer when EVERY layer is banded
        (swa/chunked); None when any layer attends the full context —
        the block tables are shared across layers, so a page is only
        freeable once no layer can ever look at it again."""
        spans: List[Tuple[str, int]] = []
        for spec in self.cfg.pattern:
            if (spec.mixer == "attn" and spec.attn_kind == "swa"
                    and self.cfg.window):
                spans.append(("swa", int(self.cfg.window)))
            elif (spec.mixer == "attn" and spec.attn_kind == "chunked"
                    and self.cfg.chunk):
                spans.append(("chunked", int(self.cfg.chunk)))
            else:
                return None
        return spans

    def _reclaim_out_of_span(self, seq: PagedSequence) -> None:
        """Decref pages wholly below every layer's attention span.

        At decode position ``pos`` an swa layer attends kv positions
        > pos - window and a chunked layer attends >= its chunk floor;
        both lower bounds are non-decreasing in pos, so once a page's
        last token falls below the minimum bound across layers no
        future query can see it.  The freed slot's block-table entry
        points at the scratch page (gathers read garbage there, the
        mask hides it) and the page returns to the pool — the paged
        path regains the ring path's sub-linear window memory."""
        if self._layer_spans is None or not self._span_reclaim:
            return
        pos = seq.pos                  # next insert/query position
        lo = None
        for kind, span in self._layer_spans:
            l = pos - span + 1 if kind == "swa" else (pos // span) * span
            lo = l if lo is None else min(lo, l)
        if lo is None or lo <= 0:
            return
        freeable = min(lo // self.pool.page_size, len(seq.pages))
        if freeable <= seq.reclaimed_upto:
            return                     # nothing new fell out of span
        freed: List[int] = []
        # resume at the watermark: slots below it are already None, so
        # the per-token scan stays O(newly freeable), not O(pages so
        # far) — a long banded generation must not go quadratic here
        for idx in range(seq.reclaimed_upto, freeable):
            pg = seq.pages[idx]
            if pg is None:
                continue               # already reclaimed
            seq.prefix_keys = self.pool.disown_prefix(seq.prefix_keys, pg)
            seq.pages[idx] = None
            seq.block_table[idx] = SCRATCH_PAGE
            freed.append(pg)
        seq.reclaimed_upto = freeable
        if freed:
            self.pool.decref(freed)
            self.reclaimed_pages += len(freed)
            self.tracer.instant("span_reclaim", track=self.trace_track,
                                args={"pages": len(freed), "pos": pos})

    # ---- probe-path prewarm -------------------------------------------
    def prewarm_logits(self, prompt) -> Optional[np.ndarray]:
        """Probe-path prewarm (the paper's probe-many-models pattern
        hits the same prompt N times): run — or reuse — the prompt's
        prefill, keep its pages resident in a small LRU of held
        sequences, and cache the final-token logits row.  A follow-up
        admission of the same prompt then takes the zero-FLOP
        logit-cache fast path.  Returns the logits row; best-effort —
        a full pool or an unpaged/uncached engine returns None."""
        if self.pool is None or self._logit_cache_cap <= 0:
            return None
        prompt_np = np.asarray(prompt, np.int32).reshape((-1,))
        if len(prompt_np) < 1:
            return None
        key = self._prompt_key(prompt_np)
        if key in self._prewarmed:
            self._prewarmed.move_to_end(key)
            return self._logit_cache_get(key)
        try:
            seq = self.prefill_into_pages(prompt_np, max_new_tokens=1)
        except (OutOfPages, ValueError):
            return None                # probe must never fail admission
        self._prewarmed[key] = seq
        while len(self._prewarmed) > self._prewarm_cap:
            _, old = self._prewarmed.popitem(last=False)
            self.pool.release(old)
        self.tracer.instant("prewarm", track=self.trace_track,
                            args={"pages": len(seq.pages),
                                  "residents": len(self._prewarmed)})
        return self._logit_cache_get(key)

    def shed_prewarmed(self) -> int:
        """Release every probe-prewarmed resident (admission calls
        this under page pressure — prewarmed pages are a cache, real
        requests outrank them).  Returns the number shed."""
        shed = 0
        while self._prewarmed:
            _, old = self._prewarmed.popitem(last=False)
            self.pool.release(old)
            shed += 1
        return shed

    def _shared_prefix(self, prompt_np: np.ndarray,
                       p: int) -> Tuple[List[int], int, int]:
        """Resident pages this prompt can map: (mapped_pages,
        matched_len, shared_len).  shared_len (the tokens *not*
        recomputed) is clamped to p - 1 — prefill must always run at
        least the final prompt token to produce next-token logits."""
        if self.pool is None or not self.pool.prefix_sharing:
            return [], 0, 0
        mapped, matched = self.pool.lookup_prefix(prompt_np)
        shared_len = min(matched, p - 1)
        if shared_len <= 0:
            return [], 0, 0
        return mapped, matched, shared_len

    def admission_page_cost(self, prompt, max_new_tokens: int, *,
                            chunk_tokens: Optional[int] = None
                            ) -> Tuple[int, int]:
        """(pages a fresh admission would allocate now, free pages to
        hold back for its future copy-on-write).  With prefix sharing
        this is the *unique*-page cost — shared pages cost nothing
        extra; the headroom is 1 when the prompt would map a
        resident's partially-filled boundary page (identical prompt),
        because decode later copies that page before inserting.

        With ``chunk_tokens`` (chunked prefill), admission budgets the
        *first chunk* rather than the whole prompt: a long prompt only
        needs its opening chunk's pages free to start prefilling —
        later chunks allocate as they run, backpressured against the
        running batch's frees."""
        prompt_np = np.asarray(prompt, np.int32).reshape((-1,))
        p = len(prompt_np)
        total = self.pool.pages_for(self._sealed_span(p, max_new_tokens))
        mapped, matched, shared_len = self._shared_prefix(prompt_np, p)
        headroom = (1 if (mapped and matched == p and p % self.pool.page_size)
                    else 0)
        if chunk_tokens is not None and shared_len + chunk_tokens < p:
            first = self.pool.pages_for(shared_len + chunk_tokens)
            return max(first - len(mapped), 0), headroom
        return total - len(mapped), headroom

    @staticmethod
    def _prompt_key(prompt_np: np.ndarray) -> bytes:
        return hashlib.sha1(
            np.ascontiguousarray(prompt_np, np.int64).tobytes()).digest()

    def _logit_cache_get(self, key: bytes) -> Optional[np.ndarray]:
        row = self._logit_cache.get(key)
        if row is not None:
            self._logit_cache.move_to_end(key)
        return row

    def _logit_cache_put(self, key: bytes, row: np.ndarray) -> None:
        if self._logit_cache_cap <= 0:
            return
        self._logit_cache[key] = row
        self._logit_cache.move_to_end(key)
        while len(self._logit_cache) > self._logit_cache_cap:
            self._logit_cache.popitem(last=False)

    # ---- resumable prefill (chunked prefill / streaming admission) ----
    def begin_prefill(self, prompt, *, max_new_tokens: int,
                      seed: Optional[int] = None,
                      temperature: Optional[float] = None,
                      stop_tokens: Sequence[int] = ()) -> PagedSequence:
        """Host-side admission of one request: validate, map any
        resident shared-prefix pages (incref), and return a *resumable*
        sequence — ``prefill_chunk`` then runs the prompt through the
        device in page-sized chunks, allocating pages as it goes, until
        the first token samples.  ``PagePool.release(seq)`` at any
        point (cancellation, failure, eviction) hands back exactly what
        the sequence holds.

        The shared-prefix lookup is *deferred* to the first
        ``prefill_chunk`` call: a burst of admissions all begun in one
        scheduler sweep can still share a prefix that the first of
        them only registers when its own prefill seals.
        """
        if self.pool is None:      # not an assert: must survive python -O
            raise RuntimeError("no paged KV pool: call init_paged() first")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1 (prefill always samples the "
                f"first token), got {max_new_tokens}")
        prompt_np = np.asarray(prompt, np.int32).reshape((-1,))
        p = len(prompt_np)
        if p < 1:
            raise ValueError("prompt must hold at least one token")
        self._check_capacity(p, max_new_tokens)
        seq_seed = self.scfg.seed if seed is None else seed
        return PagedSequence(
            pages=[],
            block_table=self.pool.block_table([], self._max_pages),
            prompt_len=p, pos=0, max_new_tokens=max_new_tokens,
            last_token=-1, seed=seq_seed, shared_prefix_len=0,
            prompt=prompt_np, prefill_pos=0, prefill_done=False,
            prefix_mapped=False, insert_from=0,
            stop_tokens=frozenset(int(t) for t in stop_tokens),
            temperature=temperature)

    def _map_shared_prefix(self, seq: PagedSequence) -> None:
        """Lazy first-chunk mapping: incref any resident shared-prefix
        pages and, on a fully-resident repeat prompt with a cached
        final-token logits row, seal the prefill with zero device FLOPs
        (the logit-cache fast path).  Runs exactly once per sequence;
        an OutOfPages from the fast path leaves the mapped pages held
        and the sequence resumable — a retry proceeds through the
        normal tail-prefill flow."""
        pool, ps = self.pool, self.pool.page_size
        p = seq.prompt_len
        mapped, matched, shared_len = self._shared_prefix(seq.prompt, p)
        seq.prefix_mapped = True
        if mapped:
            pool.incref(mapped)
            if matched == p and p % ps:
                # the resident's partially-filled boundary page is now
                # shared; whichever holder inserts into it first must
                # copy-on-write (admission reserved the headroom)
                pool.mark_cow_risk(mapped[-1])
            for i, pg in enumerate(mapped):
                seq.block_table[i] = pg
            seq.pages = list(mapped)
            seq.prefill_pos = shared_len
            seq.shared_prefix_len = shared_len
            seq.insert_from = len(mapped) * ps
        # memory hierarchy: where the device-resident prefix ends, the
        # host tier may hold the next chunks — restore them instead of
        # recomputing (matched == p never restores: fully resident)
        if self.host_tier is not None and p > 1 and matched < p:
            self._restore_from_host(seq)
        # zero-FLOP admission: fully-resident repeat prompt + cached
        # final-token logits -> skip even the one-token tail prefill
        if matched == p and self._logit_cache_cap > 0:
            row = self._logit_cache_get(self._prompt_key(seq.prompt))
            if row is not None:
                self._grow_pages(seq, pool.pages_for(
                    self._sealed_span(p, seq.max_new_tokens)))
                tok = int(np.asarray(self._sample_rows(
                    jnp.asarray(row)[None], np.asarray([seq.seed]),
                    np.asarray([p]), temps=[seq.temperature]))[0])
                self.logit_cache_hits += 1
                self.prefill_tokens_shared += p
                seq.shared_prefix_len = p
                self.tracer.instant("logit_cache_hit",
                                    track=self.trace_track,
                                    args={"prompt_len": int(p)})
                self._seal_prefill(seq, tok)

    def _sealed_span(self, p: int, max_new_tokens: int) -> int:
        """Token span a sealing prefill reserves pages for: the whole
        prompt+decode budget normally, or just prompt+1 under lazy
        decode allocation (decode steps grow page-by-page instead)."""
        return (p + 1) if self._lazy_decode_alloc else (p + max_new_tokens)

    def set_lazy_decode_alloc(self, enabled: bool) -> None:
        """Flip lazy decode allocation after ``init_paged`` (the
        scheduler pushes ``PagedLLMConfig.lazy_decode_alloc`` here at
        startup).  Only affects sequences sealed from now on — already
        sealed sequences keep whatever span they reserved."""
        self._lazy_decode_alloc = bool(enabled)

    # ---- host tier: spill / restore -----------------------------------
    def _spill_pages(self, pages: Sequence[int]):
        """Gather whole pages off the device for the host tier — the
        callback ``TieredPagePool.bind_spill`` runs during eviction
        (never under the pool lock; this takes the device lock itself).
        Returns a host-materialised package, leaves
        ``(g, max_pages, page_size, ...)`` with rows past len(pages)
        garbage (the store ignores them)."""
        with self._device_lock:
            if self._caches_poisoned:
                raise RuntimeError("paged caches poisoned: cannot spill")
            t0 = time.time()
            padded = np.full((self._max_pages,), SCRATCH_PAGE, np.int32)
            padded[:len(pages)] = pages
            package = jax.tree.map(
                np.asarray,
                self._tier_gather(self._paged_caches, jnp.asarray(padded)))
            self.tracer.span("SPILL", track=self.trace_track,
                             t0=t0, t1=time.time(),
                             args={"pages": len(pages)})
            return package

    def _restore_from_host(self, seq: PagedSequence) -> None:
        """Continue a prompt's chunk chain into the host tier: where
        the device-resident prefix ends, restore the host-resident run
        into fresh device pages (fixed-shape scatter) and advance the
        sequence as if those pages had been resident all along — the
        tail prefill then computes only what neither tier holds.
        OutOfPages on the restore allocation degrades to a plain miss
        (chunked prefill proceeds normally); a scatter failure poisons
        the caches but leaks nothing (the new pages decref, the host
        entries survive untouched)."""
        pool, ps = self.pool, self.pool.page_size
        p = seq.prompt_len
        base = len(seq.pages)       # device-mapped chunks (all full:
        #                             a matched partial means matched == p,
        #                             which never reaches here)
        run = self.host_tier.lookup(seq.prompt, start_chunk=base)
        if not run:
            return
        n = len(run)
        matched_total = p if run[-1][2] else (base + n) * ps
        shared_len = min(matched_total, p - 1)
        if shared_len <= seq.prefill_pos:
            return                  # would not advance the prefill
        try:
            new = pool.alloc(n)     # may itself spill colder pages
        except OutOfPages:
            return                  # treat as a miss, never as failure
        t0 = time.time()
        package = self.host_tier.load([s for _k, s, _pt in run],
                                      self._max_pages)
        padded = np.full((self._max_pages,), SCRATCH_PAGE, np.int32)
        padded[:n] = new
        try:
            self._paged_caches = self._tier_scatter(
                self._paged_caches, package, jnp.asarray(padded))
            jax.block_until_ready(jax.tree.leaves(self._paged_caches)[0])
        except Exception:
            self._caches_poisoned = True
            pool.decref(new)
            raise
        # the chunks are device-resident again: retire the host copies
        # (one tier owns a chunk at a time; they re-index on seal)
        self.host_tier.consume([k for k, _s, _pt in run])
        for pg in new:
            seq.block_table[len(seq.pages)] = pg
            seq.pages.append(pg)
        seq.prefill_pos = shared_len
        seq.shared_prefix_len = shared_len
        seq.insert_from = len(seq.pages) * ps
        self.tracer.span("RESTORE", track=self.trace_track,
                         t0=t0, t1=time.time(),
                         args={"pages": n, "shared_len": int(shared_len)})

    def _grow_pages(self, seq: PagedSequence, upto: int) -> None:
        """Extend ``seq`` to hold ``upto`` pages (alloc + block-table
        update).  Raises OutOfPages with nothing mutated."""
        need = upto - len(seq.pages)
        if need <= 0:
            return
        new = self.pool.alloc(need)
        for pg in new:
            seq.block_table[len(seq.pages)] = pg
            seq.pages.append(pg)

    def _seal_prefill(self, seq: PagedSequence, tok: int) -> None:
        seq.last_token = tok
        seq.tokens = [tok]
        seq.pos = seq.prompt_len
        seq.prefill_pos = seq.prompt_len
        seq.prefill_done = True
        seq.prefix_keys = self.pool.register_prefix(seq.prompt, seq.pages)

    def prefill_chunk(self, seq: PagedSequence, *,
                      chunk_tokens: Optional[int] = None) -> bool:
        """Run the next prefill chunk of a sequence started by
        ``begin_prefill``; returns True once the prompt is fully
        prefilled and the first token sampled (the sequence can then
        join a running decode batch).

        ``chunk_tokens`` (a multiple of page_size) caps this step's
        prompt span — the q_offset tail path computes positions
        ``prefill_pos .. prefill_pos + chunk - 1`` against everything
        already resident, so a scheduler can interleave one chunk per
        decode step and a long prompt never stalls running streams.
        ``chunk_tokens=None`` runs the whole remaining prompt in one
        call (the serial path).  Pages for the chunk (plus the decode
        budget, on the final chunk) allocate here; OutOfPages raises
        *before* any device work with the sequence unchanged — callers
        treat it as backpressure and retry after frees.
        """
        with self._device_lock:
            return self._prefill_chunk_locked(seq, chunk_tokens=chunk_tokens)

    def _prefill_chunk_locked(self, seq: PagedSequence, *,
                              chunk_tokens: Optional[int] = None) -> bool:
        if seq.prefill_done:
            return True
        pool = self.pool
        ps = pool.page_size
        if chunk_tokens is not None and (chunk_tokens < ps
                                         or chunk_tokens % ps):
            raise ValueError(
                f"chunk_tokens must be a positive multiple of the page "
                f"size {ps}, got {chunk_tokens}")
        if not seq.prefix_mapped:
            self._map_shared_prefix(seq)    # OutOfPages: seq resumable
            if seq.prefill_done:            # logit-cache fast path
                return True
        p = seq.prompt_len
        o = seq.prefill_pos
        length = p - o if chunk_tokens is None else min(chunk_tokens, p - o)
        final = o + length >= p
        span = (self._sealed_span(p, seq.max_new_tokens) if final
                else (o + length))
        self._grow_pages(seq, pool.pages_for(span))    # OutOfPages: no-op
        prompt = jnp.asarray(seq.prompt, jnp.int32)
        bt = jnp.asarray(seq.block_table)[None]
        try:
            if o == 0 and final:
                # whole-prompt single call (no resident prefix): the
                # classic prefill path, padded to its page rounding
                pad = pool.pages_for(p) * ps
                toks = jnp.zeros((1, pad), jnp.int32).at[0, :p].set(prompt)
                logits, self._paged_caches = self._paged_prefill(
                    self.params, toks, self._paged_caches, bt,
                    jnp.asarray(p - 1, jnp.int32))
            else:
                # q_offset tail path: positions < o are read back from
                # pages earlier chunks (or a resident shared prefix)
                # already filled; writes below ``insert_from`` are
                # redirected to scratch so a shared boundary page is
                # never touched.  A fixed chunk_tokens pad keeps every
                # chunk at ONE compiled shape (offsets are traced).
                pad = (chunk_tokens if chunk_tokens is not None
                       else pool.pages_for(length) * ps)
                toks = jnp.zeros((1, pad), jnp.int32).at[
                    0, :length].set(prompt[o:o + length])
                last = (p - 1 - o) if final else (length - 1)
                logits, self._paged_caches = self._paged_prefill_tail(
                    self.params, toks, self._paged_caches, bt,
                    jnp.asarray(last, jnp.int32),
                    jnp.asarray(o, jnp.int32),
                    jnp.asarray(seq.insert_from, jnp.int32))
            self.prefill_tokens_computed += int(pad)
            if final:
                # materialise INSIDE the guard: jax dispatch is async,
                # so an execution-time failure of the donating jit call
                # often surfaces only here
                row = np.asarray(logits)[0, 0]
                tok = int(np.asarray(self._sample_rows(
                    jnp.asarray(row)[None], np.asarray([seq.seed]),
                    np.asarray([p]), temps=[seq.temperature]))[0])
            else:
                jax.block_until_ready(
                    jax.tree.leaves(self._paged_caches)[0])
        except Exception:
            # conservatively treat any failure of the donating call as
            # cache loss; the caller still holds (and must release) the
            # sequence — its page list is exact, so release() is a
            # complete rollback
            self._caches_poisoned = True
            raise
        if final:
            self.prefill_tokens_shared += seq.shared_prefix_len
            if self._logit_cache_cap > 0:
                self.logit_cache_misses += 1
                self._logit_cache_put(self._prompt_key(seq.prompt), row)
            self._seal_prefill(seq, tok)
        else:
            seq.prefill_pos = o + length
        return seq.prefill_done

    def prefill_into_pages(self, prompt, *, max_new_tokens: int,
                           seed: Optional[int] = None,
                           temperature: Optional[float] = None,
                           stop_tokens: Sequence[int] = ()) -> PagedSequence:
        """Admit one request in one call: ``begin_prefill`` + the whole
        prompt through ``prefill_chunk`` (serial, tail-only when a
        shared prefix is resident).  The returned sequence can join a
        running decode batch immediately.

        Raises ValueError if prompt + max_new_tokens exceeds max_len,
        and OutOfPages (a ValueError) when the pool cannot hold the
        request — the scheduler treats the latter as backpressure.
        Any failure releases everything the admission held: the pool is
        exactly as it was before the call.
        """
        seq = self.begin_prefill(prompt, max_new_tokens=max_new_tokens,
                                 seed=seed, temperature=temperature,
                                 stop_tokens=stop_tokens)
        try:
            while not seq.prefill_done:
                self.prefill_chunk(seq)
        except Exception:
            self.pool.release(seq)  # failed admission must not leak pages
            raise
        return seq

    def decode_step_batch(self, seqs: Sequence[PagedSequence]) -> np.ndarray:
        """One decode step for up to ``decode_batch`` running sequences
        at *different* positions (the token-level continuous batch).
        Rows beyond len(seqs) are inactive: they write to the scratch
        page and their samples are discarded.  Advances each sequence
        in place; returns the sampled tokens (len(seqs),)."""
        with self._device_lock:
            return self._decode_step_batch_locked(seqs)

    def _decode_step_batch_locked(self, seqs: Sequence[PagedSequence]
                                  ) -> np.ndarray:
        if self.pool is None:
            raise RuntimeError("no paged KV pool: call init_paged() first")
        cap = self._decode_batch
        if len(seqs) > cap:
            raise ValueError(f"{len(seqs)} sequences > decode_batch={cap}")
        ps = self.pool.page_size
        # lazy decode-budget allocation: a sequence sealed without its
        # full decode span grows page-by-page as it advances (no-op for
        # fully-reserved sequences).  OutOfPages raises BEFORE any
        # device work with every page list exact — backpressure, not
        # corruption.
        for seq in seqs:
            try:
                self._grow_pages(seq, self.pool.pages_for(seq.pos + 1))
            except OutOfPages as exc:
                # like cow_seq below: tag the starving sequence so the
                # scheduler can fail just this request instead of the
                # whole backend (lazy decode alloc means a healthy
                # batch can hit this under plain pressure)
                exc.grow_seq = seq
                raise
        # copy-on-write, fused into the decode jit: a sequence about to
        # insert into a page other sequences still map gets a private
        # copy as part of the decode step itself (sharing must never let
        # one request's decode tokens leak into another's prefix).  Page
        # allocation happens BEFORE the donating jit — OutOfPages here
        # leaves the caches intact and only this request need fail —
        # but refcount/block-table bookkeeping is deferred until the jit
        # succeeds.  ``pending`` mirrors the decrefs that bookkeeping
        # will apply, so the second holder of a page the first row is
        # already COWing sees an effective refcount of 1 and keeps the
        # original page (exactly the sequential-copy behaviour).
        cow: List[Tuple[int, PagedSequence, int, int, int]] = []
        pending: Dict[int, int] = {}
        for i, seq in enumerate(seqs):
            idx = seq.pos // ps
            old = seq.pages[idx]
            if self.pool.refcount(old) - pending.get(old, 0) > 1:
                try:
                    new = self.pool.alloc(1)[0]
                except OutOfPages as exc:
                    # roll back this step's earlier COW allocations
                    self.pool.decref([n for _, _, _, _, n in cow])
                    exc.cow_seq = seq
                    raise
                cow.append((i, seq, idx, old, new))
                pending[old] = pending.get(old, 0) + 1
        tokens = np.zeros((cap, 1), np.int32)
        bt = np.full((cap, self._max_pages), 0, np.int32)
        pos = np.zeros((cap,), np.int32)
        seeds = np.zeros((cap,), np.uint32)
        temps: List[Optional[float]] = [None] * cap
        for i, seq in enumerate(seqs):
            tokens[i, 0] = seq.last_token
            bt[i] = seq.block_table
            pos[i] = seq.pos
            seeds[i] = np.uint32(seq.seed)
            temps[i] = seq.temperature
        # COWing rows decode against their private copy: the fused jit
        # copies old -> new across every layer slab, then the insert
        # lands in the copy (rows that don't COW ride scratch -> scratch)
        src = np.full((cap,), SCRATCH_PAGE, np.int32)
        dst = np.full((cap,), SCRATCH_PAGE, np.int32)
        for r, (i, seq, idx, old, new) in enumerate(cow):
            bt[i, idx] = new
            src[r] = old
            dst[r] = new
        try:
            if cow:
                logits, self._paged_caches = self._paged_decode_cow(
                    self.params, jnp.asarray(tokens), self._paged_caches,
                    jnp.asarray(bt), jnp.asarray(pos), jnp.asarray(src),
                    jnp.asarray(dst))
            else:
                logits, self._paged_caches = self._paged_decode(
                    self.params, jnp.asarray(tokens), self._paged_caches,
                    jnp.asarray(bt), jnp.asarray(pos))
            # row i's next token sits at position pos[i] + 1; keying
            # the sample by (seq.seed, position) keeps a sampled
            # generation independent of batch composition.  Materialise
            # inside the guard — async dispatch surfaces jit failures
            # here, after the caches were already donated.
            nxt = np.asarray(self._sample_rows(logits[:, 0], seeds, pos + 1,
                                               temps=temps))
        except Exception:
            self._caches_poisoned = True    # donated buffers are gone
            self.pool.decref([n for _, _, _, _, n in cow])
            raise
        for i, seq, idx, old, new in cow:
            # the copy diverged from the indexed prefix the moment the
            # step inserted, so this sequence stops backing entries for
            # the old page; the remaining holders keep them valid
            seq.prefix_keys = self.pool.disown_prefix(seq.prefix_keys, old)
            self.pool.decref([old])
            seq.pages[idx] = new
            seq.block_table[idx] = new
            self.cow_count += 1
            self.tracer.instant("cow", track=self.trace_track,
                                args={"old": int(old), "new": int(new),
                                      "fused": True})
        for i, seq in enumerate(seqs):
            seq.pos += 1
            seq.last_token = int(nxt[i])
            seq.tokens.append(int(nxt[i]))
            self._reclaim_out_of_span(seq)
        return nxt[:len(seqs)]

    # ---- speculative decoding: verify + draft-page rollback ----------
    def verify_step_batch(self, rows: Sequence[Tuple[PagedSequence,
                                                     Sequence[int]]],
                          *, width: int) -> List[np.ndarray]:
        """Verify up to ``decode_batch`` rows of drafted tokens in ONE
        multi-token step (the chunked-prefill traced-q_offset path with
        per-row positions).  Each row feeds
        ``[seq.last_token, d_1 .. d_k]`` at absolute positions
        ``seq.pos .. seq.pos + k`` and gets back the verifier's greedy
        pick after every fed token — ``out[i][j]`` is the token the
        verifier would emit after seeing the row's context plus drafts
        ``d_1..d_j``, so the longest matching prefix decides how many
        drafts commit.  ``width`` fixes the compiled shape (S = width
        >= k + 1 for every row; short rows right-pad).

        Sequence state is NOT advanced here — the caller commits
        accepted tokens (``spec_decode.SpeculativeBackend``).  K/V
        written above a row's finally-committed position is garbage but
        positionally masked and overwritten before ever becoming
        visible, so verifier-side rollback costs nothing; inactive and
        padded slots write the scratch page."""
        with self._device_lock:
            return self._verify_step_batch_locked(rows, width)

    def _verify_step_batch_locked(self, rows, width: int) -> List[np.ndarray]:
        if self.pool is None:
            raise RuntimeError("no paged KV pool: call init_paged() first")
        cap = self._decode_batch
        if len(rows) > cap:
            raise ValueError(f"{len(rows)} verify rows > "
                             f"decode_batch={cap}")
        for seq, drafts in rows:
            if len(drafts) + 1 > width:
                raise ValueError(f"{len(drafts)} drafts + 1 exceeds the "
                                 f"verify width {width}")
        tokens = np.zeros((cap, width), np.int32)
        bt = np.full((cap, self._max_pages), SCRATCH_PAGE, np.int32)
        q_off = np.zeros((cap,), np.int32)
        for i, (seq, drafts) in enumerate(rows):
            tokens[i, 0] = seq.last_token
            tokens[i, 1:1 + len(drafts)] = drafts
            bt[i] = seq.block_table
            q_off[i] = seq.pos
        try:
            logits, self._paged_caches = self._paged_verify(
                self.params, jnp.asarray(tokens), self._paged_caches,
                jnp.asarray(bt), jnp.asarray(q_off))
            # greedy only: speculative rows are restricted to
            # temperature <= 0 (exactness is argmax parity).
            # Materialise inside the guard — async dispatch surfaces
            # jit failures here, after the caches were donated.
            picks = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
        except Exception:
            self._caches_poisoned = True
            raise
        return [picks[i, :len(drafts) + 1]
                for i, (seq, drafts) in enumerate(rows)]

    def rollback_pages(self, seq: PagedSequence, span_tokens: int) -> int:
        """Hand back the pages of ``seq`` past the page covering
        ``span_tokens`` tokens — refcounted decref, block-table slots
        fall back to scratch.  The speculative drafter calls this after
        a verify round to free what its rejected drafts allocated; the
        page list stays exact throughout, so ``pool.release(seq)``
        after a mid-verify cancellation is still a complete rollback.
        Returns the number of pages freed."""
        keep = self.pool.pages_for(span_tokens)
        freed: List[int] = []
        while len(seq.pages) > keep:
            pg = seq.pages.pop()
            seq.block_table[len(seq.pages)] = SCRATCH_PAGE
            if pg is not None:
                seq.prefix_keys = self.pool.disown_prefix(seq.prefix_keys, pg)
                freed.append(pg)
        if freed:
            self.pool.decref(freed)
        return len(freed)

    def generate_paged(self, prompt, *, max_new_tokens: int,
                       seed: Optional[int] = None,
                       temperature: Optional[float] = None,
                       stop_tokens: Sequence[int] = ()) -> Dict[str, Any]:
        """Single-request convenience over the paged entry points
        (prefill -> solo decode batch -> release pages); the reference
        the scheduler/benchmark compare continuous batching against."""
        t0 = time.time()
        seq = self.prefill_into_pages(prompt, max_new_tokens=max_new_tokens,
                                      seed=seed, temperature=temperature,
                                      stop_tokens=stop_tokens)
        t1 = time.time()
        try:
            while not seq.done:
                self.decode_step_batch([seq])
            t2 = time.time()
        finally:
            self.pool.release(seq)      # a failed decode must not leak
        prompt_np = np.asarray(prompt, np.int32).reshape((-1,))
        tokens = np.concatenate([prompt_np, np.asarray(seq.tokens, np.int32)])
        return {"tokens": tokens, "prefill_s": t1 - t0, "decode_s": t2 - t1,
                "finish_reason": seq.finish_reason,
                "tokens_per_s": len(seq.tokens) / max(t2 - t1, 1e-9)}
