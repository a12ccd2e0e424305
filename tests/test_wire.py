"""Wire robustness: version negotiation, frame hygiene, auth.

The cluster transport's failure modes are typed and tested here,
separate from the happy-path cluster tests:

* version mismatch is rejected in BOTH directions (a legacy v1 hello
  against this server, and this client against a v1 server), with
  ``WireVersionError`` naming the versions each side speaks;
* truncated and garbage frames raise promptly instead of desyncing
  the stream;
* a client with the wrong shared secret is refused before it can
  issue a single op;
* the wire schema round-trips arbitrary JSON-shaped payloads
  (hypothesis fuzz, skipped when hypothesis is not installed).
"""
import asyncio
import threading

import numpy as np
import pytest

from repro.serving.backend import (WIRE_VERSION, WIRE_VERSIONS, BackendServer,
                                   WireVersionError, negotiate_wire_version,
                                   wire_decode, wire_encode,
                                   wire_error_payload, wire_error_rehydrate)
from repro.serving.cluster import (MAX_FRAME_BYTES, FrameError,
                                   SocketBackendServer, SocketClientBackend,
                                   encode_frame, read_frame)
from repro.serving.cluster.transport import _mac
from repro.serving.cluster.serve import build_tiny_backend


# ---------------------------------------------------------------------------
# Version negotiation, both directions
# ---------------------------------------------------------------------------

def test_negotiate_picks_newest_common():
    assert negotiate_wire_version(list(WIRE_VERSIONS)) == WIRE_VERSION
    assert negotiate_wire_version([*WIRE_VERSIONS, 99]) == WIRE_VERSION
    with pytest.raises(WireVersionError, match="this build speaks"):
        negotiate_wire_version([1])          # legacy v1 has no overlap
    with pytest.raises(WireVersionError):
        negotiate_wire_version([])


def test_v1_client_hello_rejected_by_server():
    """A legacy v1 hello (no versions list — its envelope 'v' is the
    whole claim) gets a typed rejection from this server."""
    srv = BackendServer(build_tiny_backend())

    async def main():
        with pytest.raises(WireVersionError):
            await srv._dispatch({"v": 1, "id": 0, "op": "hello", "body": {}})

    asyncio.run(main())


def test_v2_client_rejects_v1_server():
    """This client against a fake v1 server: the handshake completes,
    the hello reply claims v=1, and the client refuses with
    WireVersionError instead of limping along mis-framed."""

    async def main():
        secret = "repro-cluster"

        async def fake_v1(reader, writer):
            nonce = "00" * 16
            writer.write(encode_frame({"op": "challenge", "nonce": nonce}))
            await writer.drain()
            auth = await read_frame(reader)
            assert auth["mac"] == _mac(secret, nonce, auth["client_id"])
            writer.write(encode_frame({"op": "auth_ok", "host": "old"}))
            await writer.drain()
            hello = await read_frame(reader)
            writer.write(encode_frame({"v": 1, "id": hello["id"],
                                       "ok": {"v": 1, "page_size": 4,
                                              "num_pages": 8,
                                              "decode_batch": 1,
                                              "max_len": 32}}))
            await writer.drain()
            await reader.read()           # EOF: the client hung up
            writer.close()
            await writer.wait_closed()

        server = await asyncio.start_server(fake_v1, "127.0.0.1", 0)
        port = server.sockets[0].getsockname()[1]
        cli = SocketClientBackend("127.0.0.1", port, secret=secret,
                                  timeout_s=0.5)
        with pytest.raises(WireVersionError, match="this client speaks"):
            await cli.start()
        await cli.stop()
        server.close()
        await server.wait_closed()

    asyncio.run(main())


def test_wire_error_roundtrips_victim_tags():
    """Both request-local victim tags (cow_seq AND grow_seq) survive
    the wire: serialized to sids against the server's table, resolved
    back to mirrors on the client — the attribution the scheduler
    needs to fail one request instead of the backend."""
    from repro.serving.kv_cache import OutOfPages

    server_seq, client_mirror = object(), object()
    for tag in ("cow_seq", "grow_seq"):
        exc = OutOfPages("page pool exhausted")
        setattr(exc, tag, server_seq)
        err = wire_error_payload(exc, {7: server_seq})
        assert err["type"] == "OutOfPages"
        assert err[tag.replace("_seq", "_sid")] == 7
        back = wire_error_rehydrate(err, {7: client_mirror})
        assert isinstance(back, OutOfPages)
        assert getattr(back, tag) is client_mirror
    # an untagged error stays untagged, and unknown sids resolve to
    # nothing rather than a wrong sequence
    err = wire_error_payload(ValueError("nope"), {})
    assert "cow_sid" not in err and "grow_sid" not in err
    back = wire_error_rehydrate({"type": "OutOfPages", "msg": "x",
                                 "cow_sid": 99}, {7: client_mirror})
    assert getattr(back, "cow_seq", None) is None


# ---------------------------------------------------------------------------
# Frame hygiene
# ---------------------------------------------------------------------------

def _reader_with(data: bytes) -> asyncio.StreamReader:
    r = asyncio.StreamReader()
    r.feed_data(data)
    r.feed_eof()
    return r


def test_truncated_frame_raises_incomplete():
    async def main():
        # torn length prefix
        with pytest.raises(asyncio.IncompleteReadError):
            await read_frame(_reader_with(b"\x00\x00"))
        # full prefix, torn payload
        good = encode_frame({"op": "ping"})
        with pytest.raises(asyncio.IncompleteReadError):
            await read_frame(_reader_with(good[:-2]))

    asyncio.run(main())


def test_garbage_frames_raise_frame_error():
    async def main():
        # length prefix past the cap (a desynced or hostile stream)
        huge = (MAX_FRAME_BYTES + 1).to_bytes(4, "big")
        with pytest.raises(FrameError, match="not a frame boundary"):
            await read_frame(_reader_with(huge))
        # valid prefix, non-JSON payload
        junk = len(b"\xff\xfe!").to_bytes(4, "big") + b"\xff\xfe!"
        with pytest.raises(FrameError):
            await read_frame(_reader_with(junk))
        # valid JSON that is not an object
        arr = b"[1, 2]"
        with pytest.raises(FrameError, match="expected an object"):
            await read_frame(_reader_with(len(arr).to_bytes(4, "big") + arr))

    asyncio.run(main())


def test_encode_frame_rejects_oversized():
    with pytest.raises(FrameError, match="exceeds MAX_FRAME_BYTES"):
        encode_frame({"blob": "x" * (MAX_FRAME_BYTES + 1)})


def test_frame_round_trip():
    async def main():
        msg = {"op": "decode", "id": 7,
               "body": {"sids": np.asarray([1, 2]), "t": np.float32(0.5)}}
        out = await read_frame(_reader_with(encode_frame(msg)))
        assert out == {"op": "decode", "id": 7,
                       "body": {"sids": [1, 2], "t": 0.5}}

    asyncio.run(main())


# ---------------------------------------------------------------------------
# Auth
# ---------------------------------------------------------------------------

def test_wrong_secret_refused_before_any_op():
    async def main():
        srv = SocketBackendServer(build_tiny_backend(), secret="right",
                                  host_label="h0")
        await srv.start()
        cli = SocketClientBackend("127.0.0.1", srv.port, secret="wrong",
                                  timeout_s=0.5)
        with pytest.raises(PermissionError, match="auth rejected"):
            await cli.start()
        await cli.stop()
        assert srv.auth_failures == 1
        # the right secret still works on the same listener
        ok = SocketClientBackend("127.0.0.1", srv.port, secret="right",
                                 timeout_s=0.5)
        await ok.start()
        assert ok.connected
        await ok.stop()
        await srv.close()

    asyncio.run(main())


def test_stop_returns_while_a_finished_release_task_is_listed():
    """A release task that finished in the loop pass where stop() began
    is still in the client's task set until its discard callback runs.
    stop() must not spin on it: gathering finished tasks never yields
    to the loop, so that callback would never run.  The client runs in
    a thread so a spinning stop() fails the test instead of hanging it."""
    stopped = threading.Event()

    async def main():
        cli = SocketClientBackend("127.0.0.1", 1)
        finished = asyncio.ensure_future(asyncio.sleep(0))
        await finished
        cli._release_tasks.add(finished)   # listed, no discard callback
        await cli.stop()
        stopped.set()

    threading.Thread(target=asyncio.run, args=(main(),), daemon=True).start()
    assert stopped.wait(timeout=10.0), "stop() spun on a finished task"


# ---------------------------------------------------------------------------
# Schema fuzz (optional dependency)
# ---------------------------------------------------------------------------

def test_wire_schema_fuzz_round_trip():
    hypothesis = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    json_values = st.recursive(
        st.none() | st.booleans()
        | st.integers(min_value=-2**53, max_value=2**53)
        | st.floats(allow_nan=False, allow_infinity=False)
        | st.text(max_size=20),
        lambda inner: st.lists(inner, max_size=4)
        | st.dictionaries(st.text(max_size=8), inner, max_size=4),
        max_leaves=20)
    msgs = st.dictionaries(st.text(min_size=1, max_size=8), json_values,
                           max_size=6)

    @hypothesis.given(msgs)
    @hypothesis.settings(max_examples=50, deadline=None)
    def round_trips(msg):
        assert wire_decode(wire_encode(msg)) == msg

    round_trips()
