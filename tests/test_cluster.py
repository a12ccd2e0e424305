"""Pod-scale cluster serving (repro.serving.cluster).

The acceptance bar from the cluster PR, as tests:

* router-over-sockets outputs are token-identical to a local
  InProcessBackend, in both streaming and request/response decode;
* killing one host mid-decode fails exactly that host's in-flight
  requests with BACKEND_LOST while the survivors' outputs stay
  bitwise identical to the local reference;
* a repeated-prefix trace routes >= 90% of the repeats to the host
  that already holds the prefix;
* a release that races a connection loss is retried across the
  reconnect and leaks zero pages on the server;
* probe-based eviction takes a dead host out of placement, and a
  restarted host is re-admitted and serves again.

Every host runs the deterministic tiny model from
``repro.serving.cluster.serve.build_tiny_backend`` — same seed, same
params — so "token-identical" is a meaningful cross-process claim.
"""
import asyncio

import jax
import numpy as np
import pytest

from repro.serving.backend import BackendLost, InProcessBackend
from repro.serving.cluster import (ClusterRouter, SocketBackendServer,
                                   SocketClientBackend)
from repro.serving.cluster.serve import build_tiny_backend
from repro.serving.kv_cache import OutOfPages
from repro.serving.observability import Tracer
from repro.serving.scheduler import (BACKEND_LOST, PagedLLMConfig,
                                     PagedLLMScheduler, SamplingParams)

PS = 4      # page size in build_tiny_backend


def prompt_of(n, fold=0):
    return np.asarray(jax.random.randint(
        jax.random.fold_in(jax.random.key(5), fold), (n,), 0, 64))


async def start_cluster(n_hosts=2, *, host_tier_pages=0, streaming=True,
                        probe_interval_s=10.0, **router_kw):
    """N socket servers (in-process) + their clients behind one router.
    probe_interval_s defaults high so tests drive ``probe_hosts()``
    deterministically."""
    servers = []
    for i in range(n_hosts):
        srv = SocketBackendServer(
            build_tiny_backend(host_tier_pages=host_tier_pages),
            host_label=f"h{i}")
        await srv.start()
        servers.append(srv)
    clients = [SocketClientBackend("127.0.0.1", s.port, name=f"sock:h{i}",
                                   streaming=streaming, heartbeat_s=0.1,
                                   timeout_s=0.5)
               for i, s in enumerate(servers)]
    router = ClusterRouter(clients, decode_batch_hint=8,
                           probe_interval_s=probe_interval_s, **router_kw)
    return servers, router


async def run_local(prompts, max_new_tokens):
    """The single-host reference the cluster must match bitwise."""
    backend = InProcessBackend(build_tiny_backend().engine)
    sched = PagedLLMScheduler(backends=[backend],
                              cfg=PagedLLMConfig(prefill_chunk_pages=1))
    async with sched:
        handles = [sched.submit(p, SamplingParams(
            max_new_tokens=max_new_tokens)) for p in prompts]
        return [np.asarray(await h) for h in handles]


# ---------------------------------------------------------------------------
# Token identity over the wire
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("streaming", [True, False],
                         ids=["streaming", "reqresp"])
def test_router_over_sockets_token_identical(streaming):
    """Outputs through socket transport + router == local backend, in
    both decode modes (per-sweep pushes and request/response)."""
    prompts = [prompt_of(9, f) for f in range(6)]

    async def main():
        louts = await run_local(prompts, 6)
        servers, router = await start_cluster(streaming=streaming)
        sched = PagedLLMScheduler(backends=[router],
                                  cfg=PagedLLMConfig(prefill_chunk_pages=1))
        async with sched:
            handles = [sched.submit(p, SamplingParams(max_new_tokens=6))
                       for p in prompts]
            couts = [np.asarray(await h) for h in handles]
        for srv in servers:
            assert srv.inner.stats()["pool"]["pages_in_use"] == 0
            await srv.close()
        for lo, co in zip(louts, couts):
            assert np.array_equal(lo, co)
        # both hosts actually served (least-loaded spread, not failover)
        st = router.stats()["cluster"]
        assert st["hosts_live"] == 2
        assert st["requests_lost"] == 0

    asyncio.run(main())


# ---------------------------------------------------------------------------
# Partial failure: one host dies mid-decode
# ---------------------------------------------------------------------------

def test_host_kill_mid_decode_isolates_failure():
    """Close one server while all four requests are decoding: the two
    requests placed there fail with BACKEND_LOST (they never hang),
    the two survivors finish bitwise identical to local."""
    prompts = [prompt_of(9, f) for f in range(4)]

    async def main():
        louts = await run_local(prompts, 24)
        servers, router = await start_cluster()
        sched = PagedLLMScheduler(backends=[router],
                                  cfg=PagedLLMConfig(prefill_chunk_pages=1))
        async with sched:
            handles = [sched.submit(p, SamplingParams(max_new_tokens=24))
                       for p in prompts]
            while any(h._req.first_token_t <= 0 for h in handles):
                await asyncio.sleep(0.01)
            await servers[1].close()
            results = await asyncio.gather(
                *(h.result() for h in handles), return_exceptions=True)
        reasons = [h._req.finish_reason for h in handles]
        lost = [i for i, r in enumerate(results)
                if isinstance(r, BaseException)]
        assert lost, "expected at least one request on the killed host"
        for i, res in enumerate(results):
            if i in lost:
                assert isinstance(res, BackendLost), res
                assert reasons[i] == BACKEND_LOST
            else:
                assert reasons[i] == "length"
                assert np.array_equal(np.asarray(res), louts[i])
        st = router.stats()["cluster"]
        assert st["requests_lost"] == len(lost)
        await servers[0].close()

    asyncio.run(main())


# ---------------------------------------------------------------------------
# Prefix-aware placement
# ---------------------------------------------------------------------------

def test_prefix_aware_placement_routes_repeats():
    """After one request seeds a 4-page prefix on a host (retained by
    its host tier), >= 90% of repeated-prefix arrivals route there."""
    prefix = np.arange(1, 17, dtype=np.int32) % 64

    def with_suffix(i):
        return np.concatenate(
            [prefix, np.asarray([17 + i, 18 + i], np.int32)])

    async def main():
        servers, router = await start_cluster(host_tier_pages=32)
        sched = PagedLLMScheduler(backends=[router],
                                  cfg=PagedLLMConfig(prefill_chunk_pages=1))
        async with sched:
            await sched.submit(with_suffix(0),
                               SamplingParams(max_new_tokens=3))
            await router.probe_hosts()      # gossip the new digest
            seeded = [i for i, h in enumerate(router.hosts) if h.digest]
            assert len(seeded) == 1
            before = router.prefix_routed
            for i in range(1, 11):
                await sched.submit(with_suffix(i),
                                   SamplingParams(max_new_tokens=3))
            routed = router.prefix_routed - before
            assert routed >= 9, f"only {routed}/10 repeats chased the prefix"
            # and the digest holder computed the shared chunks once
            hs = router.hosts[seeded[0]]
            assert hs.prefill_tokens_shared == 0     # refreshed by probe
            await router.probe_hosts()
            assert router.hosts[seeded[0]].prefill_tokens_shared > 0
        for srv in servers:
            await srv.close()

    asyncio.run(main())


def test_prefix_aware_placement_computes_fewer_prefill_tokens():
    """The same repeated-prefix trace, one request at a time with a
    probe after each, on two fresh hosts under prefix-aware and under
    least-loaded placement: identical outputs, and strictly fewer
    prefill tokens computed across the hosts when placement chases the
    prefix."""
    prefix = np.asarray(prompt_of(16, 11))
    prompts = [np.concatenate([prefix, np.asarray([(17 + i) % 64,
                                                   (29 + i) % 64],
                                                  np.int32)])
               for i in range(8)]

    async def serve(prefix_aware):
        servers, router = await start_cluster(host_tier_pages=32,
                                              prefix_aware=prefix_aware)
        sched = PagedLLMScheduler(backends=[router],
                                  cfg=PagedLLMConfig(prefill_chunk_pages=1))
        outs = []
        async with sched:
            for p in prompts:
                outs.append(np.asarray(await sched.submit(
                    p, SamplingParams(max_new_tokens=3))))
                await router.probe_hosts()
            st = router.stats()["cluster"]
        for srv in servers:
            await srv.close()
        return outs, sum(h["prefill_tokens_computed"]
                         for h in st["per_host"])

    async def main():
        return await serve(True), await serve(False)

    (pa_outs, pa_tokens), (lb_outs, lb_tokens) = asyncio.run(main())
    for a, b in zip(pa_outs, lb_outs):
        np.testing.assert_array_equal(a, b)
    assert pa_tokens < lb_tokens, (pa_tokens, lb_tokens)


def test_serve_subprocess_host_is_token_identical_to_local():
    """``python -m repro.serving.cluster.serve`` in its own process
    prints ``LISTENING <port>`` and, reached over the socket, serves
    bitwise the tokens of a local backend built with the same flags."""
    import os
    import signal
    import subprocess
    import sys

    import repro.serving
    src = os.path.abspath(os.path.join(
        os.path.dirname(repro.serving.__file__), "..", ".."))
    path = os.pathsep.join(filter(None, [src, os.environ.get("PYTHONPATH")]))
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.serving.cluster.serve", "--port", "0",
         "--host-label", "sub0"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": path})
    prompts = [prompt_of(9, f) for f in range(3)]
    try:
        line = proc.stdout.readline().strip()
        assert line.startswith("LISTENING "), line
        port = int(line.split()[1])

        async def main():
            louts = await run_local(prompts, 5)
            client = SocketClientBackend("127.0.0.1", port, name="sock:sub0",
                                         streaming=True, heartbeat_s=1.0)
            sched = PagedLLMScheduler(
                backends=[client], cfg=PagedLLMConfig(prefill_chunk_pages=1))
            async with sched:
                couts = [np.asarray(await sched.submit(
                    p, SamplingParams(max_new_tokens=5))) for p in prompts]
            return louts, couts

        louts, couts = asyncio.run(main())
        for lo, co in zip(louts, couts):
            np.testing.assert_array_equal(lo, co)
    finally:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
        proc.stdout.close()
    assert proc.returncode == 0


def test_load_shedding_overrides_prefix_affinity():
    """A hot prefix host does not absorb unbounded load: once its load
    passes shed_factor * (min + 1), placement falls back to
    least-loaded even though the prefix scores higher there."""

    async def main():
        servers, router = await start_cluster(host_tier_pages=32,
                                              shed_factor=1.0)
        await router.start()
        prompt = np.arange(1, 17, dtype=np.int32) % 64
        # fake a digest so host 0 wins every prefix score, then load it
        from repro.serving.kv_cache import PagePool, chunk_keys
        keys = {k.hex()[:PagePool.DIGEST_HEX]
                for k, partial in chunk_keys(prompt.tolist(), PS)
                if not partial}
        router.hosts[0].digest = keys
        router.hosts[0].queue_depth = 8          # deeply backed up
        hs = router._place(prompt.tolist())
        assert hs is router.hosts[1]
        assert router.shed_overrides == 1
        await router.stop()
        for srv in servers:
            await srv.close()

    asyncio.run(main())


# ---------------------------------------------------------------------------
# Release retry across reconnect: zero leaked pages
# ---------------------------------------------------------------------------

def test_release_retry_spans_reconnect_no_leak():
    """Drop the connection immediately before release: the acked
    release retries across the transport's reconnect and the server
    ends with zero pages in use and no pending releases."""

    async def main():
        inner = build_tiny_backend()
        srv = SocketBackendServer(inner, host_label="hz")
        await srv.start()
        cli = SocketClientBackend("127.0.0.1", srv.port,
                                  heartbeat_s=0.1, timeout_s=0.5)
        await cli.start()
        seq = cli.begin(prompt_of(9), max_new_tokens=4)
        while not await cli.prefill_chunk(seq, chunk_tokens=PS):
            pass
        assert inner.stats()["pool"]["pages_in_use"] > 0
        cli._writer.close()          # the pipe dies under the release
        cli.release(seq)
        for _ in range(100):
            await asyncio.sleep(0.05)
            if (inner.stats()["pool"]["pages_in_use"] == 0
                    and not cli._pending_releases):
                break
        assert inner.stats()["pool"]["pages_in_use"] == 0
        assert not cli._pending_releases
        assert cli.reconnects >= 1
        await cli.stop()
        await srv.close()

    asyncio.run(main())


def test_streaming_sweep_error_keeps_victim_attribution():
    """A request-local OutOfPages raised inside the streamed sweep
    reaches the client WITH its victim (cow_seq resolved back to the
    mirror) — that attribution is what lets the scheduler fail one
    request instead of killing the backend.  And after the error, a
    decode_batch with identical membership re-declares the stream set
    instead of waiting forever on a sweep the server dropped."""

    async def main():
        inner = build_tiny_backend()
        srv = SocketBackendServer(inner, host_label="hx")
        await srv.start()
        cli = SocketClientBackend("127.0.0.1", srv.port,
                                  heartbeat_s=0.1, timeout_s=0.5)
        await cli.start()
        s1 = cli.begin(prompt_of(9, 0), max_new_tokens=4)
        s2 = cli.begin(prompt_of(9, 1), max_new_tokens=4)
        for s in (s1, s2):
            while not await cli.prefill_chunk(s, chunk_tokens=PS):
                pass
        # sabotage exactly one sweep: a COW-tagged OutOfPages against
        # the first server-side sequence, then restore real decode
        real = inner.decode_batch

        async def boom(seqs):
            inner.decode_batch = real
            exc = OutOfPages("no free page for copy-on-write")
            exc.cow_seq = seqs[0]
            raise exc

        inner.decode_batch = boom
        with pytest.raises(OutOfPages) as ei:
            await asyncio.wait_for(cli.decode_batch([s1, s2]), timeout=5)
        assert getattr(ei.value, "cow_seq", None) is s1
        # same membership again: must re-declare and decode, not hang
        out = await asyncio.wait_for(cli.decode_batch([s1, s2]), timeout=5)
        assert out.shape == (2,)
        for s in (s1, s2):
            cli.release(s)
        for _ in range(100):
            await asyncio.sleep(0.02)
            if inner.stats()["pool"]["pages_in_use"] == 0:
                break
        assert inner.stats()["pool"]["pages_in_use"] == 0
        await cli.stop()
        await srv.close()

    asyncio.run(main())


def test_release_pends_through_outage_then_acks():
    """A release during an outage is never dropped by an attempt
    budget: the sid stays in _pending_releases (stats would expose a
    real leak) and the retry acks once a server answers again."""

    async def main():
        inner = build_tiny_backend()
        srv = SocketBackendServer(inner, host_label="hy")
        await srv.start()
        port = srv.port
        cli = SocketClientBackend("127.0.0.1", port,
                                  heartbeat_s=0.05, timeout_s=0.3)
        await cli.start()
        seq = cli.begin(prompt_of(9), max_new_tokens=4)
        while not await cli.prefill_chunk(seq, chunk_tokens=PS):
            pass
        await srv.close()                 # outage begins
        cli.release(seq)
        await asyncio.sleep(0.6)          # several failed attempts later
        assert cli._pending_releases == {seq.sid}
        assert cli.stats()["pending_releases"] == 1
        # a fresh server on the same port: the client reconnects and
        # the retried release finally acks (unknown sid = clean no-op)
        srv2 = SocketBackendServer(build_tiny_backend(), port=port,
                                   host_label="hy")
        await srv2.start()
        for _ in range(200):
            await asyncio.sleep(0.05)
            if not cli._pending_releases:
                break
        assert not cli._pending_releases
        await cli.stop()
        await srv2.close()

    asyncio.run(main())


def test_default_secret_refuses_non_loopback(monkeypatch):
    """Without an operator-chosen secret the HMAC handshake is
    decorative, so a non-loopback bind refuses to start; loopback and
    explicit secrets still construct fine."""
    monkeypatch.delenv("REPRO_CLUSTER_SECRET", raising=False)

    async def main():
        srv = SocketBackendServer(object(), host="0.0.0.0")
        with pytest.raises(ValueError, match="non-loopback"):
            await srv.start()

    asyncio.run(main())
    # an explicit secret (arg or env) is what unlocks non-loopback
    assert not SocketBackendServer(object(), host="0.0.0.0",
                                   secret="s3cret")._secret_is_default
    monkeypatch.setenv("REPRO_CLUSTER_SECRET", "s3cret")
    assert not SocketBackendServer(object(),
                                   host="0.0.0.0")._secret_is_default


def test_place_skips_hosts_that_can_never_fit():
    """Placement only considers hosts whose pool can ever hold the
    request: a small-pool host never gets pinned a request it would
    spin on, even when it wins the load tie-break and prefix score."""

    async def main():
        srv_small = SocketBackendServer(build_tiny_backend(num_pages=4),
                                        host_label="small")
        srv_big = SocketBackendServer(build_tiny_backend(),
                                      host_label="big")
        await srv_small.start()
        await srv_big.start()
        clients = [SocketClientBackend("127.0.0.1", srv_small.port,
                                       name="sock:small"),
                   SocketClientBackend("127.0.0.1", srv_big.port,
                                       name="sock:big")]
        router = ClusterRouter(clients, probe_interval_s=10.0)
        await router.start()
        prompt = list(range(1, 21))       # 20 + 8 tokens = 7 pages > 4
        # stack the deck for the small host: idle, and prefix-affine
        from repro.serving.kv_cache import PagePool, chunk_keys
        router.hosts[0].digest = {
            k.hex()[:PagePool.DIGEST_HEX]
            for k, partial in chunk_keys(prompt, PS) if not partial}
        router.hosts[1].queue_depth = 3
        assert router._place(prompt, 8) is router.hosts[1]
        # a request both pools can hold still follows load
        assert router._place(list(range(8)), 4) is router.hosts[0]
        await router.stop()
        await srv_small.close()
        await srv_big.close()

    asyncio.run(main())


# ---------------------------------------------------------------------------
# Probe eviction and re-admission
# ---------------------------------------------------------------------------

def test_probe_eviction_and_readmission():
    """A host that stops answering probes is evicted after the miss
    budget; the same host restarted on the same port is re-admitted
    and serves again."""

    async def main():
        servers, router = await start_cluster()
        sched = PagedLLMScheduler(backends=[router],
                                  cfg=PagedLLMConfig(prefill_chunk_pages=1))
        async with sched:
            port1 = servers[1].port
            await servers[1].close()
            await router.probe_hosts()
            await router.probe_hosts()
            assert [h.live for h in router.hosts] == [True, False]
            assert router.evictions == 1
            # evicted host never receives placements
            for _ in range(4):
                assert router._place(list(range(8))) is router.hosts[0]
            # restart on the same port -> transport reconnects, probe
            # readmits
            srv1b = SocketBackendServer(build_tiny_backend(),
                                        port=port1, host_label="h1")
            await srv1b.start()
            servers[1] = srv1b
            for _ in range(100):
                await router.probe_hosts()
                if router.hosts[1].live:
                    break
                await asyncio.sleep(0.05)
            assert router.hosts[1].live
            assert router.readmissions == 1
            out = await sched.submit(prompt_of(9, 7),
                                     SamplingParams(max_new_tokens=3))
            assert np.asarray(out).shape[0] == 12
        for srv in servers:
            await srv.close()

    asyncio.run(main())


# ---------------------------------------------------------------------------
# Observability: host labels and the cluster snapshot block
# ---------------------------------------------------------------------------

def test_tracer_host_label_prefixes_tracks(tmp_path):
    """A host-labelled tracer namespaces every track, so merged
    multi-host traces render one process group per host."""
    tr = Tracer(host="h7")
    tr.instant("boot", track="sched")
    tr.counter("pool", {"free": 3}, track="gauges/pool")
    tracks = {ev[3] for ev in tr.events()}
    assert tracks == {"h7:sched", "h7:gauges/pool"}
    doc = tr.export(str(tmp_path / "t.json"))
    assert doc["otherData"]["host"] == "h7"


def test_snapshot_surfaces_cluster_counters():
    """PagedLLMScheduler.snapshot() flattens the router's cluster
    stats into cluster_* keys plus a per-host detail list."""

    async def main():
        servers, router = await start_cluster()
        sched = PagedLLMScheduler(backends=[router],
                                  cfg=PagedLLMConfig(prefill_chunk_pages=1))
        async with sched:
            await sched.submit(prompt_of(9), SamplingParams(max_new_tokens=3))
            snap = sched.snapshot()
        assert snap["cluster_hosts"] == 2
        assert snap["cluster_hosts_live"] == 2
        assert snap["cluster_requests_lost"] == 0
        assert snap["cluster_prefix_routed"] + snap["cluster_load_routed"] >= 1
        detail = snap["cluster_hosts_detail"]
        assert {d["host"] for d in detail} == {"sock:h0", "sock:h1"}
        for srv in servers:
            await srv.close()

    asyncio.run(main())
