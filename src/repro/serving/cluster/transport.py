"""Socket transport for the cluster serving tier.

``DuplexChannel`` (repro.serving.backend) is an in-process stand-in:
two asyncio queues of wire-encoded strings.  This module is the real
thing — the same JSON wire schema carried over TCP as length-prefixed
frames, with everything a transport needs that a queue pair never
does:

* **Framing.**  Every message is ``[4-byte big-endian length][UTF-8
  JSON]``.  A frame longer than :data:`MAX_FRAME_BYTES`, a torn
  length prefix, or a payload that does not parse raises
  :class:`FrameError` — the connection is dropped, never interpreted.
* **Auth.**  On accept the server sends a random nonce; the client
  answers with HMAC-SHA256(secret, nonce + client_id).  Constant-time
  compare; a bad MAC closes the connection before any op runs.  The
  secret is shared out of band (``REPRO_CLUSTER_SECRET``).
* **Sessions.**  Server-side sequence state is keyed by ``client_id``,
  not by connection: a client that reconnects (same id) adopts its
  old session, so sequences survive a transport blip and the acked
  release retry loop can still free them — a lost release frame never
  leaks pages.
* **Heartbeats.**  The client pings on an interval; silence past
  ``timeout_s`` (no frame of any kind) kills the connection and
  triggers reconnect with bounded exponential backoff.  On loss every
  begun, unfinished mirror is marked ``done`` with the
  ``BACKEND_LOST`` finish reason — in-flight requests FAIL promptly,
  they never hang on a dead socket.
* **Streaming decode.**  Instead of one decode round-trip per token,
  the client declares its running set (``stream_set``) and the server
  sweeps it in a loop, pushing each sweep's ``new_tokens`` rows as
  unsolicited ``push`` frames the moment they exist.  The client's
  ``decode_batch`` just waits for the next push — remote inter-token
  latency tracks local ITL instead of adding a round trip per token.
* **Flow control.**  The push stream is credit-gated: the client acks
  each push (``push_ack``) after applying it, and the sweep loop stays
  at most ``stream_window`` pushes ahead.  A slow consumer throttles
  decode instead of filling socket buffers; with the default window
  of 1 the producer and consumer strictly alternate, which also keeps
  a core-starved box from carving timeslice holes into the cadence.

``SocketBackendServer`` wraps any ``ModelBackend`` behind a listening
socket (one ``BackendServer`` dispatcher per client session);
``python -m repro.serving.cluster.serve`` runs one per host.
``SocketClientBackend`` is the scheduler-facing half — a drop-in
``ModelBackend`` whose every data-plane call crosses the socket.
"""
from __future__ import annotations

import asyncio
import dataclasses
import hashlib
import hmac
import ipaddress
import itertools
import os
import socket
import time
from typing import Any, Dict, List, Optional

import numpy as np

from repro.serving.backend import (BackendCapacity, BackendLost,
                                   BackendServer, ModelBackend,
                                   RemoteSequence, WIRE_VERSION,
                                   WIRE_VERSIONS, WireVersionError,
                                   _WIRE_ERRORS, wire_decode, wire_encode,
                                   wire_error_payload,
                                   wire_error_rehydrate)
from repro.serving.observability.tracer import NULL_TRACER, backend_track
from repro.serving.scheduler.request import BACKEND_LOST

#: hard bound on one frame's payload (a 9-token prompt is ~100 bytes;
#: the largest real frame is a begin payload or a digest gossip — a
#: length prefix beyond this is garbage, not a message)
MAX_FRAME_BYTES = 1 << 24

#: dev-only shared secret when the operator sets none — anyone who can
#: read the source knows it, so it makes the HMAC handshake decorative.
#: Acceptable on loopback (same-box tests/dev); a server binding a
#: non-loopback address with it REFUSES to start.  Real deployments
#: export REPRO_CLUSTER_SECRET on every host.
DEFAULT_SECRET = "repro-cluster"
SECRET_ENV = "REPRO_CLUSTER_SECRET"


def _is_loopback(host: str) -> bool:
    if host == "localhost":
        return True
    try:
        return ipaddress.ip_address(host).is_loopback
    except ValueError:
        return False                      # hostname / wildcard: assume not


class FrameError(RuntimeError):
    """The byte stream does not parse as a frame (oversized length
    prefix, truncated payload, or non-JSON bytes): drop the
    connection, never guess."""


def encode_frame(msg: Dict[str, Any]) -> bytes:
    payload = wire_encode(msg).encode("utf-8")
    if len(payload) > MAX_FRAME_BYTES:
        raise FrameError(f"frame of {len(payload)} bytes exceeds "
                         f"MAX_FRAME_BYTES={MAX_FRAME_BYTES}")
    return len(payload).to_bytes(4, "big") + payload


async def read_frame(reader: asyncio.StreamReader) -> Dict[str, Any]:
    """One frame off the stream.  Raises FrameError on garbage,
    ``asyncio.IncompleteReadError`` on truncation (peer went away
    mid-frame)."""
    header = await reader.readexactly(4)
    n = int.from_bytes(header, "big")
    if n > MAX_FRAME_BYTES:
        raise FrameError(f"length prefix {n} exceeds "
                         f"MAX_FRAME_BYTES={MAX_FRAME_BYTES} — "
                         f"not a frame boundary")
    payload = await reader.readexactly(n)
    try:
        msg = wire_decode(payload.decode("utf-8"))
    except (UnicodeDecodeError, ValueError) as exc:
        raise FrameError(f"frame payload is not wire JSON: {exc}") from exc
    if not isinstance(msg, dict):
        raise FrameError(f"frame decodes to {type(msg).__name__}, "
                         f"expected an object")
    return msg


async def _drain_close(writer: asyncio.StreamWriter) -> None:
    """Close a writer and wait for the transport to actually die.
    ``close()`` alone only schedules the teardown on the loop — a loop
    that exits first abandons the transport to the GC, which warns
    (and fails ``-W error`` test runs) about the unclosed socket."""
    writer.close()
    try:
        await writer.wait_closed()
    except Exception:                     # noqa: BLE001 — already dead
        pass


def _mac(secret: str, nonce: str, client_id: str) -> str:
    return hmac.new(secret.encode("utf-8"),
                    (nonce + client_id).encode("utf-8"),
                    hashlib.sha256).hexdigest()


# ---------------------------------------------------------------------------
# Server
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _Session:
    """One client's server-side state, keyed by client_id so it
    survives reconnects (the new connection adopts it)."""
    server: BackendServer
    writer: Optional[asyncio.StreamWriter] = None
    stream_sids: List[int] = dataclasses.field(default_factory=list)
    wake: asyncio.Event = dataclasses.field(default_factory=asyncio.Event)
    sweep_task: Optional[asyncio.Task] = None
    tasks: set = dataclasses.field(default_factory=set)
    # credit-based flow control for the push stream: the sweep loop
    # stays at most ``stream_window`` unacked pushes ahead of the
    # client, so a slow consumer throttles decode instead of watching
    # tokens pile up in socket buffers (and on a box with fewer cores
    # than processes, the enforced producer/consumer alternation keeps
    # the two sides from being runnable at once — which is what lets
    # the OS carve multi-ms timeslice holes into the token cadence)
    unacked: int = 0
    credit: asyncio.Event = dataclasses.field(default_factory=asyncio.Event)


class SocketBackendServer:
    """One host's serving endpoint: any ``ModelBackend`` behind a
    listening TCP socket, one wire-dispatch session per client_id."""

    def __init__(self, inner: ModelBackend, *, host: str = "127.0.0.1",
                 port: int = 0, secret: Optional[str] = None,
                 host_label: str = "host", stream_window: int = 1):
        self.inner = inner
        self.bind_host = host
        self.port = port                  # 0 -> kernel assigns; see start()
        env_secret = os.environ.get(SECRET_ENV)
        self.secret = (secret if secret is not None
                       else env_secret if env_secret is not None
                       else DEFAULT_SECRET)
        # nobody chose this secret: fine on loopback, refused off it
        self._secret_is_default = secret is None and env_secret is None
        self.host_label = host_label
        # max unacked pushes before the sweep loop waits for the
        # client; 1 = lockstep (lowest jitter), raise it to overlap
        # decode with client-side processing on multi-core hosts
        self.stream_window = max(1, int(stream_window))
        self._decode_warm = False         # first sweep compiles off-loop
        self._server: Optional[asyncio.AbstractServer] = None
        self._sessions: Dict[str, _Session] = {}
        self.auth_failures = 0
        self.frame_errors = 0

    async def start(self) -> None:
        if self._secret_is_default and not _is_loopback(self.bind_host):
            raise ValueError(
                f"refusing to serve on non-loopback address "
                f"{self.bind_host!r} with the dev default secret — any "
                f"peer that read the source could authenticate.  Export "
                f"{SECRET_ENV} (same value on every host) or pass "
                f"secret= explicitly.")
        await self.inner.start()
        self._server = await asyncio.start_server(
            self._handle, self.bind_host, self.port)
        self.port = self._server.sockets[0].getsockname()[1]

    @staticmethod
    def _no_delay(writer: asyncio.StreamWriter) -> None:
        """Frames are small and latency-critical (a decode push per
        sweep); letting Nagle coalesce them would put milliseconds of
        batching delay on every inter-token gap."""
        sock = writer.get_extra_info("socket")
        if sock is not None and sock.family in (socket.AF_INET,
                                                socket.AF_INET6):
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)

    async def close(self) -> None:
        """Stop listening, kill sweeps, reclaim every session's
        sequences, and stop the inner backend."""
        server, self._server = self._server, None
        if server is not None:
            server.close()                # stop accepting connections
        for sess in self._sessions.values():
            if sess.sweep_task is not None:
                sess.sweep_task.cancel()
            for t in list(sess.tasks):
                t.cancel()
            if sess.writer is not None:
                await _drain_close(sess.writer)
            sess.server.reclaim()
        self._sessions.clear()
        if server is not None:
            # since Python 3.12 this also waits for every open
            # connection to end, so it comes after the pipes are closed
            await server.wait_closed()
        await self.inner.stop()

    # ---- connection handling ------------------------------------------
    async def _handle(self, reader: asyncio.StreamReader,
                      writer: asyncio.StreamWriter) -> None:
        self._no_delay(writer)
        try:
            client_id = await self._auth(reader, writer)
        except Exception:
            self.auth_failures += 1
            await _drain_close(writer)
            return
        if client_id is None:
            self.auth_failures += 1
            await _drain_close(writer)
            return
        sess = self._sessions.get(client_id)
        if sess is None:
            sess = self._sessions[client_id] = _Session(
                BackendServer(self.inner))
        if sess.writer is not None:
            sess.writer.close()           # reconnect replaces the old pipe
        sess.writer = writer
        sess.unacked = 0                  # old pipe's acks are never coming
        sess.credit.set()
        if sess.sweep_task is None or sess.sweep_task.done():
            sess.sweep_task = asyncio.ensure_future(self._sweep(sess))
        sess.wake.set()
        try:
            await self._serve_session(sess, reader, writer)
        finally:
            if sess.writer is writer:
                sess.writer = None        # session stays; pipe is gone
                sess.unacked = 0
                sess.credit.set()         # unblock the sweep to park
            await _drain_close(writer)

    async def _auth(self, reader, writer) -> Optional[str]:
        nonce = os.urandom(16).hex()
        writer.write(encode_frame({"op": "challenge", "nonce": nonce,
                                   "versions": list(WIRE_VERSIONS)}))
        await writer.drain()
        msg = await asyncio.wait_for(read_frame(reader), timeout=5.0)
        client_id = str(msg.get("client_id", ""))
        if (msg.get("op") != "auth" or not client_id
                or not hmac.compare_digest(
                    str(msg.get("mac", "")),
                    _mac(self.secret, nonce, client_id))):
            writer.write(encode_frame({"op": "auth_err",
                                       "msg": "bad credentials"}))
            await writer.drain()
            return None
        writer.write(encode_frame({"op": "auth_ok",
                                   "host": self.host_label}))
        await writer.drain()
        return client_id

    def _send(self, sess: _Session, msg: Dict[str, Any]) -> None:
        """One frame to the session's live pipe; silently dropped when
        the client is between connections (it will resync on
        reconnect — every op is either retried or re-declared)."""
        w = sess.writer
        if w is None or w.is_closing():
            return
        try:
            w.write(encode_frame(msg))
        except (ConnectionError, RuntimeError):
            pass

    def _reply(self, sess: _Session, msg: Dict[str, Any], ok,
               err: Optional[Dict[str, Any]] = None) -> None:
        reply: Dict[str, Any] = {
            "v": WIRE_VERSION, "id": msg.get("id"),
            "healthy": self.inner.healthy,
            "cap": dataclasses.asdict(self.inner.capacity()),
            "host": self.host_label,
        }
        if err is None:
            reply["ok"] = ok
        else:
            reply["err"] = err
        self._send(sess, reply)

    async def _serve_session(self, sess: _Session, reader, writer) -> None:
        while True:
            try:
                msg = await read_frame(reader)
            except (asyncio.IncompleteReadError, ConnectionError):
                return                    # clean-enough disconnect
            except FrameError:
                self.frame_errors += 1
                return                    # garbage: drop the pipe
            op = msg.get("op")
            if op == "ping":
                self._reply(sess, msg, {"pong": True})
            elif op == "push_ack":
                sess.unacked = max(0, sess.unacked - 1)
                sess.credit.set()
            elif op == "stream_set":
                sess.stream_sids = [int(s) for s in
                                    msg.get("body", {}).get("sids", [])]
                sess.wake.set()
                self._reply(sess, msg, {"streaming": len(sess.stream_sids)})
            elif op == "shutdown":
                reclaimed = sess.server.reclaim()
                sess.stream_sids = []
                self._reply(sess, msg, {"reclaimed": reclaimed})
                return
            else:
                # dispatch concurrently: a long prefill must not block
                # this loop from answering pings (the client's liveness
                # clock) or release retries
                task = asyncio.ensure_future(self._dispatch_one(sess, msg))
                sess.tasks.add(task)
                task.add_done_callback(sess.tasks.discard)

    async def _dispatch_one(self, sess: _Session, msg) -> None:
        try:
            ok = await sess.server._dispatch(msg)
        except asyncio.CancelledError:
            raise
        except Exception as exc:          # noqa: BLE001 — wire it
            self._reply(sess, msg, None,
                        err=wire_error_payload(exc, sess.server._seqs))
            return
        self._reply(sess, msg, ok)

    # ---- streaming sweep ----------------------------------------------
    async def _sweep(self, sess: _Session) -> None:
        """The streaming decode loop: sweep the session's declared set
        and push each sweep's new tokens the moment they exist — no
        per-token round trip.  Pauses (event-waits) whenever the set
        is empty or the client is between connections."""
        def live_set():
            seqs = [(sid, sess.server._seqs.get(sid))
                    for sid in sess.stream_sids]
            return [(sid, s) for sid, s in seqs
                    if s is not None and s.prefill_done and not s.done]

        while True:
            live = live_set()
            if not live or sess.writer is None:
                sess.wake.clear()
                # re-check after clear: a stream_set may have landed
                # between the scan and the clear
                if not (live_set() and sess.writer is not None):
                    await sess.wake.wait()
                continue
            if sess.unacked >= self.stream_window:
                # out of credit: the client hasn't digested what we
                # already pushed — wait for its ack instead of racing
                # ahead (the timeout is a resync backstop, not a path)
                sess.credit.clear()
                if sess.unacked >= self.stream_window:
                    try:
                        await asyncio.wait_for(sess.credit.wait(),
                                               timeout=2.0)
                    except asyncio.TimeoutError:
                        sess.unacked = 0
                continue
            before = [len(s.tokens) for _, s in live]
            # the sweep is this loop's whole job, so when the inner
            # backend exposes its engine AND its executor is idle,
            # decode directly instead of paying an executor hop per
            # sweep — the engine's device lock keeps it safe, and
            # ~half a millisecond comes off every inter-token gap.
            # Two cases still defer to the executor path: ops in
            # flight (a prefill chunk, say), where the direct call
            # would block the event loop on the device lock and starve
            # the very frames feeding those ops; and a cold engine,
            # where the first decode carries the XLA compile (hundreds
            # of ms) — on the loop that silence would outlast client
            # heartbeat timeouts and read as a dead host.  Decode pads
            # to a fixed decode_batch shape, so one executor-side
            # decode compiles everything the direct path will run.
            eng = getattr(self.inner, "engine", None)
            fast_decode = getattr(eng, "decode_step_batch", None)
            try:
                if (fast_decode is not None and self._decode_warm
                        and getattr(self.inner, "_inflight", 1) == 0):
                    tracer = getattr(self.inner, "_tracer", NULL_TRACER)
                    with tracer.region(
                            "repro.remote.decode_sweep",
                            backend_track(self.inner.name, "decode")):
                        fast_decode([s for _, s in live])
                else:
                    await self.inner.decode_batch([s for _, s in live])
                    self._decode_warm = True
            except Exception as exc:      # noqa: BLE001 — wire it
                # serialize exactly like the request/response path:
                # the victim tags (cow_sid/grow_sid) are what let the
                # client rehydrate a request-local OutOfPages — without
                # them the scheduler reads it as a backend death and
                # kills every request on this host
                self._send(sess, {"op": "push", "rows": [],
                                  "err": wire_error_payload(
                                      exc, sess.server._seqs)})
                sess.stream_sids = []
                continue
            rows = [dict(sess.server._state_of(s), sid=sid,
                         new_tokens=[int(t) for t in s.tokens[n0:]])
                    for (sid, s), n0 in zip(live, before)]
            w = sess.writer
            if w is not None and not w.is_closing():
                sess.unacked += 1         # consumed on the client's ack
            self._send(sess, {"op": "push", "rows": rows,
                              "t_mono": time.monotonic(),
                              "healthy": self.inner.healthy,
                              "cap": dataclasses.asdict(
                                  self.inner.capacity())})
            w = sess.writer
            if w is not None:
                try:
                    await w.drain()       # flow control: don't outrun TCP
                except (ConnectionError, RuntimeError):
                    pass
            done_sids = {sid for sid, s in live if s.done}
            if done_sids:
                sess.stream_sids = [sid for sid in sess.stream_sids
                                    if sid not in done_sids]
            # yield so freshly-arrived frames (release, stream_set)
            # interleave with back-to-back sweeps
            await asyncio.sleep(0)
            # and yield the CPU itself: this loop is compute-bound, so
            # on a box with fewer cores than host processes the client
            # only gets scheduled when our timeslice expires — pushes
            # then arrive in timeslice-sized bursts and the client's
            # inter-token p99 balloons.  One voluntary switch per sweep
            # (~µs) lets the client drain the push we just sent.
            os.sched_yield()


# ---------------------------------------------------------------------------
# Client
# ---------------------------------------------------------------------------

_client_ids = itertools.count()


class SocketClientBackend(ModelBackend):
    """Scheduler-facing ``ModelBackend`` whose server lives across a
    socket.  Mirrors ``RemoteStubBackend``'s protocol use exactly —
    same begin/prefill/decode/release ops, same mirror-sequence
    bookkeeping — plus the transport concerns: auth, heartbeat,
    reconnect with bounded backoff, streaming decode, and marking
    every in-flight mirror ``BACKEND_LOST`` the moment the pipe dies
    so no request ever hangs on a dead host."""

    def __init__(self, host: str, port: int, *,
                 secret: Optional[str] = None,
                 name: Optional[str] = None,
                 client_id: Optional[str] = None,
                 streaming: bool = True,
                 heartbeat_s: float = 0.25,
                 timeout_s: float = 2.0,
                 reconnect: bool = True,
                 reconnect_min_s: float = 0.05,
                 reconnect_max_s: float = 1.0,
                 digest_cap: int = 2048):
        self.host = host
        self.port = port
        self.secret = secret if secret is not None else os.environ.get(
            SECRET_ENV, DEFAULT_SECRET)
        self.name = name or f"sock:{host}:{port}"
        self.client_id = client_id or (
            f"client-{os.getpid()}-{next(_client_ids)}")
        self.streaming = streaming
        self.heartbeat_s = float(heartbeat_s)
        self.timeout_s = float(timeout_s)
        self.reconnect = reconnect
        self.reconnect_min_s = float(reconnect_min_s)
        self.reconnect_max_s = float(reconnect_max_s)
        self.digest_cap = int(digest_cap)

        self._reader: Optional[asyncio.StreamReader] = None
        self._writer: Optional[asyncio.StreamWriter] = None
        self._supervisor_task: Optional[asyncio.Task] = None
        self._heartbeat_task: Optional[asyncio.Task] = None
        self._stopping = False
        self._pending: Dict[int, asyncio.Future] = {}
        self._ids = itertools.count()
        self._sids = itertools.count()
        self._mirrors: Dict[int, RemoteSequence] = {}
        self._cap = BackendCapacity(decode_batch=1)
        self._geom: Dict[str, Any] = {}
        self._healthy = False
        self._last_rx = 0.0
        self._push_event = asyncio.Event()
        self._stream_err: Optional[Dict[str, Any]] = None
        self._stream_sent: Optional[List[int]] = None
        self.server_host_label: Optional[str] = None
        self.last_status: Dict[str, Any] = {}
        self.messages_sent = 0
        self.reconnects = 0
        self.losses = 0                   # connection-loss events
        self._pending_releases: set = set()
        self._release_tasks: set = set()

    # ---- lifecycle -----------------------------------------------------
    async def start(self) -> None:
        self._stopping = False
        await self._connect()             # first connect failure is fatal
        self._supervisor_task = asyncio.ensure_future(self._supervisor())
        self._heartbeat_task = asyncio.ensure_future(self._heartbeat())

    async def stop(self) -> None:
        self._stopping = True
        # let release acks land: shutdown reclaims leftovers anyway but
        # an abandoned retry task dies noisily with the loop.  Wait on
        # the unfinished tasks only: a finished one stays in the set
        # until its discard callback runs, and gathering finished tasks
        # completes without yielding, so that callback would never run
        while pending := [t for t in self._release_tasks if not t.done()]:
            await asyncio.gather(*pending, return_exceptions=True)
        if self.connected:
            try:
                await asyncio.wait_for(self._call("shutdown"),
                                       timeout=self.timeout_s)
            except Exception:             # noqa: BLE001 — best effort
                pass
        for task in (self._heartbeat_task, self._supervisor_task):
            if task is not None:
                task.cancel()
                try:
                    await task
                except asyncio.CancelledError:
                    pass
        self._heartbeat_task = self._supervisor_task = None
        w = self._writer
        self._teardown_pipe()
        if w is not None:                 # don't abandon it to the GC
            try:
                await w.wait_closed()
            except Exception:             # noqa: BLE001 — already dead
                pass

    @property
    def connected(self) -> bool:
        return self._writer is not None and not self._writer.is_closing()

    @property
    def healthy(self) -> bool:
        return self.connected and self._healthy

    # ---- connection machinery -----------------------------------------
    async def _connect(self) -> None:
        reader, writer = await asyncio.open_connection(self.host, self.port)
        SocketBackendServer._no_delay(writer)
        try:
            challenge = await asyncio.wait_for(read_frame(reader),
                                               timeout=self.timeout_s)
            if challenge.get("op") != "challenge":
                raise FrameError(f"expected challenge, got "
                                 f"{challenge.get('op')!r}")
            writer.write(encode_frame({
                "op": "auth", "client_id": self.client_id,
                "mac": _mac(self.secret, str(challenge["nonce"]),
                            self.client_id)}))
            await writer.drain()
            verdict = await asyncio.wait_for(read_frame(reader),
                                             timeout=self.timeout_s)
            if verdict.get("op") != "auth_ok":
                raise PermissionError(
                    f"auth rejected by {self.host}:{self.port}: "
                    f"{verdict.get('msg', verdict.get('op'))}")
            self.server_host_label = verdict.get("host")
            # hello inline (the read loop is not running yet): write
            # the frame, read its reply straight off the stream
            mid = next(self._ids)
            writer.write(encode_frame({"v": WIRE_VERSION, "id": mid,
                                       "op": "hello",
                                       "body": {"versions":
                                                list(WIRE_VERSIONS)}}))
            await writer.drain()
            self.messages_sent += 1
            reply = await asyncio.wait_for(read_frame(reader),
                                           timeout=self.timeout_s)
            if "err" in reply:
                err = reply["err"]
                raise _WIRE_ERRORS.get(err["type"],
                                       RuntimeError)(err["msg"])
            geom = reply["ok"]
            if geom.get("v") not in WIRE_VERSIONS:
                raise WireVersionError(
                    f"wire version mismatch: server negotiated "
                    f"{geom.get('v')}, this client speaks "
                    f"{sorted(WIRE_VERSIONS)}")
            self._apply_envelope(reply)
        except BaseException:
            await _drain_close(writer)
            raise
        self._geom = geom
        self._reader, self._writer = reader, writer
        self._healthy = True
        self._last_rx = time.monotonic()
        self._stream_sent = None          # server set died with the pipe
        self._stream_err = None

    def _teardown_pipe(self) -> None:
        if self._writer is not None:
            self._writer.close()
        self._reader = self._writer = None
        self._healthy = False

    def _on_conn_lost(self) -> None:
        """The pipe died: every begun, unfinished mirror is marked
        BACKEND_LOST (requests fail promptly, never hang) and every
        in-flight call errors.  Server-side state survives under our
        client_id — release retries will still free it after
        reconnect."""
        self._teardown_pipe()
        self.losses += 1
        lost = 0
        for seq in self._mirrors.values():
            if seq.begun and not seq.done:
                seq.done = True
                seq.finish_reason = BACKEND_LOST
                lost += 1
        for fut in self._pending.values():
            if not fut.done():
                fut.set_exception(BackendLost(
                    f"connection to {self.name} lost"))
        self._pending.clear()
        self._push_event.set()            # wake streaming waiters: done
        if self._tracer.enabled:
            self._tracer.instant("cluster_conn_lost",
                                 args={"backend": self.name,
                                       "mirrors_lost": lost})

    async def _supervisor(self) -> None:
        """Owns the read loop; on loss, reconnects with bounded
        exponential backoff (sessions are adopted server-side, so a
        reconnect is invisible to everything but in-flight calls)."""
        backoff = self.reconnect_min_s
        while not self._stopping:
            try:
                await self._read_loop()
            except asyncio.CancelledError:
                raise
            except Exception:             # noqa: BLE001 — pipe died
                pass
            if self._writer is not None:
                self._on_conn_lost()
            if self._stopping or not self.reconnect:
                return
            while not self._stopping:
                await asyncio.sleep(backoff)
                backoff = min(backoff * 2, self.reconnect_max_s)
                try:
                    await self._connect()
                except asyncio.CancelledError:
                    raise
                except Exception:         # noqa: BLE001 — keep trying
                    continue
                self.reconnects += 1
                backoff = self.reconnect_min_s
                if self._tracer.enabled:
                    self._tracer.instant("cluster_reconnect",
                                         args={"backend": self.name,
                                               "n": self.reconnects})
                break

    async def _read_loop(self) -> None:
        reader = self._reader
        while reader is not None and reader is self._reader:
            msg = await read_frame(reader)
            self._last_rx = time.monotonic()
            self._apply_envelope(msg)
            if msg.get("op") == "push":
                self._apply_push(msg)
                continue
            fut = self._pending.pop(msg.get("id"), None)
            if fut is not None and not fut.done():
                fut.set_result(msg)

    def _apply_envelope(self, msg: Dict[str, Any]) -> None:
        if "healthy" in msg:
            self._healthy = bool(msg["healthy"])
        if "cap" in msg:
            self._cap = BackendCapacity(**msg["cap"])

    def _apply_push(self, msg: Dict[str, Any]) -> None:
        if msg.get("err"):
            self._stream_err = msg["err"]
            # the server dropped its sweep set with this error: forget
            # ours too, else a next decode_batch with identical
            # membership would skip re-declaring and wait forever on a
            # sweep that is no longer running
            self._stream_sent = None
        for row in msg.get("rows", ()):
            seq = self._mirrors.get(row.get("sid"))
            if seq is not None and not seq.done:
                seq.apply(row)
        self._push_event.set()
        # return the flow-control credit only after the rows are
        # applied: the server's next sweep is gated on this ack
        w = self._writer
        if w is not None and not w.is_closing():
            try:
                w.write(encode_frame({"op": "push_ack"}))
            except (ConnectionError, RuntimeError):
                pass

    async def _heartbeat(self) -> None:
        while not self._stopping:
            await asyncio.sleep(self.heartbeat_s)
            if not self.connected:
                continue
            if time.monotonic() - self._last_rx > self.timeout_s:
                # silence past the deadline: the pipe is dead even if
                # TCP hasn't noticed.  Close (don't tear down) so the
                # supervisor's read loop errors out and runs the ONE
                # loss path — mirrors marked lost, reconnect begins
                self._writer.close()
                continue
            try:
                await asyncio.wait_for(self._call("ping"),
                                       timeout=self.timeout_s)
            except asyncio.CancelledError:
                raise
            except Exception:             # noqa: BLE001 — loss path owns it
                pass

    # ---- calls ---------------------------------------------------------
    async def _call(self, op: str, body: Optional[Dict] = None,
                    timeout: Optional[float] = None) -> Dict[str, Any]:
        if not self.connected:
            raise BackendLost(f"backend {self.name!r} is not connected")
        mid = next(self._ids)
        fut = asyncio.get_running_loop().create_future()
        self._pending[mid] = fut
        self.messages_sent += 1
        tracer = self._tracer
        t0 = tracer.clock() if tracer.enabled else 0.0
        try:
            self._writer.write(encode_frame(
                {"v": WIRE_VERSION, "id": mid, "op": op,
                 "body": body or {}}))
        except (ConnectionError, RuntimeError) as exc:
            self._pending.pop(mid, None)
            raise BackendLost(f"send to {self.name!r} failed: {exc}")
        if timeout is None:
            msg = await fut
        else:
            try:
                msg = await asyncio.wait_for(fut, timeout)
            finally:
                self._pending.pop(mid, None)
        if tracer.enabled:
            tracer.span(op, backend_track(self.name, "wire"), t0,
                        tracer.clock(), {"mid": mid})
        if "err" in msg:
            raise wire_error_rehydrate(msg["err"], self._mirrors)
        return msg["ok"]

    async def status(self, timeout: Optional[float] = None
                     ) -> Dict[str, Any]:
        """One status round trip (queue depth, sequence count, prefix
        digest) — the router's probe.  Caches the reply for placement
        scoring between probes."""
        st = await self._call("status", {"digest_cap": self.digest_cap},
                              timeout=timeout)
        self.last_status = st
        return st

    # ---- token-level surface ------------------------------------------
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
            max_new_tokens=max_new_tokens, seed=seed,
            temperature=temperature,
            stop_tokens=tuple(int(t) for t in stop_tokens))
        self._mirrors[seq.sid] = seq
        return seq

    async def prefill_chunk(self, seq, *, chunk_tokens=None) -> bool:
        if seq.done and seq.finish_reason == BACKEND_LOST:
            raise BackendLost(f"sequence {seq.sid} was lost with its "
                              f"connection to {self.name!r}")
        body: Dict[str, Any] = {"sid": seq.sid, "chunk_tokens": chunk_tokens}
        if not seq.begun:
            deadline_t = getattr(seq, "deadline_t", None)
            body["begin"] = {"prompt": seq.prompt.tolist(),
                             "max_new_tokens": seq.max_new_tokens,
                             "seed": seq.seed,
                             "temperature": seq.temperature,
                             "stop_tokens": list(seq.stop_tokens),
                             "deadline_rel": (
                                 None if deadline_t is None
                                 else max(0.0,
                                          deadline_t - time.monotonic()))}
            seq.begun = True              # release must fire regardless
        ok = await self._call("prefill_chunk", body)
        seq.apply(ok["state"])
        return ok["done"]

    async def decode_batch(self, seqs):
        if self.streaming:
            return await self._decode_streaming(seqs)
        ok = await self._call("decode", {"sids": [s.sid for s in seqs]})
        out = []
        for seq, row in zip(seqs, ok["rows"]):
            seq.apply(row)
            out.append(seq.tokens[-1])
        return np.asarray(out, np.int32)

    async def _decode_streaming(self, seqs):
        """Wait for the server's sweep loop instead of asking for a
        token: declare the set once (re-declared only when membership
        changes or after reconnect) and return as soon as ANY sequence
        grew or finished — the scheduler's multi-token commit path
        absorbs whatever accumulated."""
        counts0 = [len(s.tokens) for s in seqs]
        sids = [s.sid for s in seqs]
        # raise a latched sweep error BEFORE re-declaring: the error's
        # victim may already be retired client-side, and re-starting
        # the sweep with it would only reproduce the failure
        self._raise_stream_err()
        if sids != self._stream_sent:
            await self._call("stream_set", {"sids": sids})
            self._stream_sent = list(sids)
        while True:
            self._raise_stream_err()
            if any(len(s.tokens) > n0 or s.done
                   for s, n0 in zip(seqs, counts0)):
                break
            self._push_event.clear()
            await self._push_event.wait()
        return np.asarray([s.tokens[-1] if s.tokens else -1
                           for s in seqs], np.int32)

    def _raise_stream_err(self) -> None:
        """Re-raise a latched sweep error with its victim attribution
        restored (``cow_seq``/``grow_seq`` resolved through the mirror
        table) — the scheduler's OutOfPages recovery fails only the
        tagged sequence instead of the whole backend."""
        if self._stream_err is None:
            return
        err, self._stream_err = self._stream_err, None
        # the server dropped its sweep set with this error; _apply_push
        # already forgot ours, but an in-flight stream_set declaration
        # may have re-recorded itself AFTER that (its reply resolved
        # before the err push was applied) — reset here too so the next
        # decode_batch always re-declares instead of waiting forever
        self._stream_sent = None
        raise wire_error_rehydrate(err, self._mirrors)

    def release(self, seq) -> None:
        self._mirrors.pop(seq.sid, None)
        if not seq.begun:
            return
        seq.begun = False
        # acked-with-retry: only the server's {"released": ...} reply
        # forgets the sid; a release racing a reconnect is re-sent
        # against the adopted session, so it cannot leak pages
        self._pending_releases.add(seq.sid)
        task = asyncio.ensure_future(self._release_with_retry(seq.sid))
        self._release_tasks.add(task)
        task.add_done_callback(self._release_tasks.discard)

    async def _release_with_retry(self, sid: int) -> None:
        # retried until acked — never a fixed attempt budget: the
        # reconnect loop tolerates arbitrarily long outages, so a
        # bounded retry would silently drop the release (and leak the
        # server-side sequence and its pages) on any outage that
        # outlasts it.  The only exit without an ack is shutdown,
        # where the server's session reclaim owns the leftovers; the
        # sid then STAYS in _pending_releases so stats expose what was
        # never confirmed.
        backoff = 0.05
        while not self._stopping:
            if not self.connected:
                # between connections: wait out the reconnect loop
                # instead of burning sends that cannot succeed
                await asyncio.sleep(backoff)
                backoff = min(backoff * 2, 0.5)
                continue
            try:
                await self._call("release", {"sid": sid},
                                 timeout=self.timeout_s)
            except asyncio.CancelledError:
                raise
            except Exception:   # noqa: BLE001 — transport hiccup: retry
                await asyncio.sleep(backoff)
                backoff = min(backoff * 2, 0.5)
                continue
            self._pending_releases.discard(sid)
            return

    # ---- admission / control plane ------------------------------------
    def capacity(self) -> BackendCapacity:
        return self._cap

    def prefix_digest(self, cap: int = 2048) -> List[str]:
        return list(self.last_status.get("digest", ()))[:cap]

    def stats(self) -> Dict[str, Any]:
        return {"name": self.name, "healthy": self.healthy,
                "connected": self.connected,
                "wire_messages": self.messages_sent,
                "reconnects": self.reconnects,
                "losses": self.losses,
                "pending_releases": len(self._pending_releases)}
