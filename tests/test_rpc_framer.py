"""Native wire framer: C scanner parity, arena scatter/gather, syscall
batching, fallback, and chaos composition (see ISSUE 7 / docs/data_plane
"Native framer").

Covers:
- scanner correctness under adversarial fragmentation (every split point
  of a raw header, random fragment fuzz) against a msgpack oracle
- wire parity: the same raw-payload workloads pass under native/native,
  python/python AND mixed native<->python endpoints (the wire format is
  one format)
- the recv takeover scatters big payloads natively (io_stats pins it)
  and small payloads / chaos-planned links keep the buffered path
- one submit-wave of frames leaves in <= 2 transport submissions
  (vectored writev in native mode)
- deterministic fallback: a corrupt .so degrades to pure Python with a
  single warning, never an error
- copies-per-byte pinned for pull (0 extra) and swarm partial serve
  (exactly 1 by design)
- mixed-mode CLUSTER: a pure-Python-framer node pulls from a native node
  and runs submit_batch waves from a native driver
"""

import asyncio
import os
import random

import msgpack
import numpy as np
import pytest

import ray_tpu
from ray_tpu._private import rpc, rpcframe

needs_native = pytest.mark.native_framer


def _skip_without_native():
    if not rpcframe.available():
        pytest.skip("native framer unavailable (no compiler?)")


@pytest.fixture(autouse=True)
def _native_marker_guard(request):
    if request.node.get_closest_marker("native_framer") is not None:
        _skip_without_native()
    yield


@pytest.fixture
def clean_rpc():
    yield
    rpc.enable_link_chaos("")
    rpc.enable_native_framer(None)


# --------------------------------------------------------------- scanner ----
def _pack(o):
    return msgpack.packb(o, use_bin_type=True)


def _scan_stream(frags):
    """Feed fragments through a Scanner + msgpack oracle; return the
    decoded (ctrl, obj) / (raw, rid, payload) sequence."""
    sc = rpcframe.Scanner()
    unp = msgpack.Unpacker(raw=False, strict_map_key=False)
    got, raw_cur = [], None
    try:
        for frag in frags:
            pos = 0
            while pos < len(frag):
                nev, consumed = sc.scan(frag, pos)
                assert nev >= 0, "scanner flagged a well-formed stream"
                assert consumed > 0 or nev > 0
                mv = memoryview(frag)
                for k in range(nev):
                    t, a, b = sc.evt[k], sc.eva[k], sc.evb[k]
                    if t == rpcframe.EV_CTRL:
                        unp.feed(mv[pos + a:pos + a + b])
                        for m in unp:
                            got.append(("ctrl", m))
                    elif t == rpcframe.EV_STASH_CTRL:
                        unp.feed(sc.spill_bytes(a, b))
                        for m in unp:
                            got.append(("ctrl", m))
                    elif t == rpcframe.EV_RAW_BEGIN:
                        raw_cur = [a, b, bytearray()]
                        if b == 0:
                            got.append(("raw", a, b""))
                            raw_cur = None
                    else:
                        raw_cur[2] += mv[pos + a:pos + a + b]
                        if len(raw_cur[2]) == raw_cur[1]:
                            got.append(("raw", raw_cur[0],
                                        bytes(raw_cur[2])))
                            raw_cur = None
                pos += consumed
    finally:
        sc.close()
    return got


@needs_native
def test_scanner_every_split_point_of_a_raw_header():
    """The stash path (raw header split anywhere, including inside the
    [rid, nbytes] ints) must reassemble exactly — a desync here corrupts
    the stream."""
    stream = (_pack([1, "x", None])
              + _pack([0, "__raw__", [-77, 13]]) + b"A" * 13
              + _pack([0, "__raw__", [900000, 0]])
              + _pack([2, "y", [1, 2]]))
    exp = [("ctrl", [1, "x", None]), ("raw", -77, b"A" * 13),
           ("raw", 900000, b""), ("ctrl", [2, "y", [1, 2]])]
    for cut in range(1, len(stream)):
        assert _scan_stream([stream[:cut], stream[cut:]]) == exp, cut
    assert _scan_stream([stream[i:i + 1]
                         for i in range(len(stream))]) == exp


@needs_native
def test_scanner_fragmentation_fuzz():
    rng = random.Random(7)
    stream, exp = b"", []
    for i in range(60):
        r = rng.random()
        if r < 0.45:
            obj = [i, f"m{i}", {"k": "v" * rng.randrange(0, 80),
                                "n": rng.randrange(-2**40, 2**40),
                                "f": 1.5, "t": True, "z": None}]
            stream += _pack(obj)
            exp.append(("ctrl", obj))
        elif r < 0.55:
            obj = [0, "notify7", None]     # 7-char name: magic-prefix stress
            stream += _pack(obj)
            exp.append(("ctrl", obj))
        else:
            rid = rng.randrange(-5000, 5000)
            n = rng.randrange(0, 4096)
            payload = bytes(rng.randrange(256) for _ in range(64))
            payload = (payload * ((n // 64) + 1))[:n]
            stream += _pack([0, "__raw__", [rid, n]]) + payload
            exp.append(("raw", rid, payload))
    assert _scan_stream([stream]) == exp
    for _ in range(60):
        frags, pos = [], 0
        while pos < len(stream):
            n = rng.randrange(1, 37) if rng.random() < 0.7 \
                else rng.randrange(1, 4096)
            frags.append(stream[pos:pos + n])
            pos += n
        assert _scan_stream(frags) == exp


@needs_native
def test_scanner_rejects_malformed_stream():
    sc = rpcframe.Scanner()
    try:
        nev, _ = sc.scan(b"\xc1\x00\x00")      # 0xc1 is not msgpack
        assert nev == -1
    finally:
        sc.close()


@needs_native
def test_scanner_aborts_on_malformed_raw_header_like_python_framer():
    """Once the __raw__ magic matches, a structurally bad [rid, nbytes]
    must flag the stream (-1 -> connection abort), NOT reclassify as a
    control frame — the pure-Python framer raises a typed RpcError
    here, and reclassifying would desync the following payload bytes
    into the frame parser."""
    bad = [
        _pack([0, "__raw__", [5, -13]]),          # negative nbytes
        _pack([0, "__raw__", [5, None]]),         # non-int nbytes
        _pack([0, "__raw__", ["x", 7]]),          # non-int rid
        _pack([0, "__raw__", {"rid": 1}]),        # third elem not a pair
    ]
    for frame in bad:
        sc = rpcframe.Scanner()
        try:
            nev, _ = sc.scan(frame + b"\xee" * 32)
            assert nev == -1, frame.hex()
        finally:
            sc.close()
        # ... and split across chunks (the stash path) too.
        sc = rpcframe.Scanner()
        try:
            nev, _ = sc.scan(frame[:12])
            if nev >= 0:
                nev, _ = sc.scan(frame[12:] + b"\xee" * 8)
            assert nev == -1, frame.hex()
        finally:
            sc.close()


# ----------------------------------------------------------- wire parity ----
MODES = [("native", "native"), ("python", "python"),
         ("native", "python"), ("python", "native")]


@pytest.mark.parametrize("srv_mode,cli_mode", MODES,
                         ids=["nn", "pp", "np", "pn"])
def test_raw_roundtrip_parity_and_mixed(srv_mode, cli_mode):
    """The raw scatter/upload/interleave workload of test_data_plane,
    across every endpoint mode combination: byte-compatible on the wire
    is the mixed-cluster guarantee."""
    if "native" in (srv_mode, cli_mode):
        _skip_without_native()
    s_nat, c_nat = srv_mode == "native", cli_mode == "native"

    async def main():
        payload = bytes(range(256)) * 2048     # 512 KiB

        async def h_fetch(conn, p):
            off, ln = p["offset"], p["length"]
            return rpc.RawPayload([memoryview(payload)[off:off + ln]])

        async def h_up(conn, p):
            blob = await conn.take_raw(p["raw_id"], timeout=10)
            return {"n": len(blob), "head": blob[:16]}

        srv = rpc.RpcServer({"fetch": h_fetch, "up": h_up},
                            name="parity", auth_token=None, native=s_nat)
        addr = await srv.start_tcp("127.0.0.1", 0)
        conn = await rpc.connect(tuple(addr), auth_token=None,
                                 native=c_nat)
        try:
            assert conn._use_native == (c_nat and rpcframe.available())
            dests = [bytearray(65536) for _ in range(6)]
            ops = [conn.call_raw("fetch",
                                 {"offset": i * 7, "length": 65536},
                                 memoryview(d))
                   for i, d in enumerate(dests)]
            ops.append(conn.call("fetch", {"offset": 5, "length": 100}))
            out = await asyncio.gather(*ops)
            assert out[:6] == [65536] * 6
            for i, d in enumerate(dests):
                assert bytes(d) == payload[i * 7:i * 7 + 65536]
            assert out[6] == payload[5:105]
            blob = np.random.default_rng(1).bytes(2_000_000)
            res = await conn.call_with_raw(
                "up", {}, rpc.RawPayload([blob]), timeout=30)
            assert res == {"n": len(blob), "head": blob[:16]}
        finally:
            await conn.close()
            await srv.close()

    asyncio.run(main())


# --------------------------------------------------------- recv takeover ----
@needs_native
def test_native_recv_takeover_scatters_into_sink():
    async def main():
        payload = np.random.default_rng(0).bytes(8 << 20)

        async def h_fetch(conn, p):
            return rpc.RawPayload([memoryview(payload)])

        srv = rpc.RpcServer({"fetch": h_fetch}, name="tko",
                            auth_token=None, native=True)
        addr = await srv.start_tcp("127.0.0.1", 0)
        conn = await rpc.connect(tuple(addr), auth_token=None, native=True)
        try:
            dest = bytearray(len(payload))
            n = await conn.call_raw("fetch", {}, memoryview(dest),
                                    timeout=60)
            assert n == len(payload) and bytes(dest) == payload
            assert conn.io_stats["rx_takeovers"] >= 1
            assert conn.io_stats["rx_native_bytes"] > len(payload) // 2
            # Normal traffic resumes cleanly after a takeover, and
            # interleaves with further takeovers.
            srv.handlers["echo"] = lambda c, p: p
            dests = [bytearray(len(payload)) for _ in range(2)]
            ops = [conn.call_raw("fetch", {}, memoryview(d), timeout=60)
                   for d in dests]
            ops += [conn.call("echo", {"i": i}) for i in range(10)]
            out = await asyncio.gather(*ops)
            assert out[:2] == [len(payload)] * 2
            assert all(bytes(d) == payload for d in dests)
            assert out[2:] == [{"i": i} for i in range(10)]
        finally:
            await conn.close()
            await srv.close()

    asyncio.run(main())


@needs_native
def test_small_payloads_skip_takeover():
    async def main():
        payload = b"z" * 4096                  # < NATIVE_RECV_MIN

        async def h_fetch(conn, p):
            return rpc.RawPayload([payload])

        srv = rpc.RpcServer({"fetch": h_fetch}, name="small",
                            auth_token=None, native=True)
        addr = await srv.start_tcp("127.0.0.1", 0)
        conn = await rpc.connect(tuple(addr), auth_token=None, native=True)
        try:
            for _ in range(4):
                dest = bytearray(len(payload))
                n = await conn.call_raw("fetch", {}, memoryview(dest),
                                        timeout=30)
                assert n == len(payload) and bytes(dest) == payload
            assert conn.io_stats["rx_takeovers"] == 0
        finally:
            await conn.close()
            await srv.close()

    asyncio.run(main())


@needs_native
def test_takeover_disengages_under_inbound_link_chaos(clean_rpc):
    """Inbound chaos plans require the buffered delayed-delivery path;
    the native framer must keep scanning but never bypass the plan —
    delays still apply, bytes still arrive intact."""
    async def main():
        payload = np.random.default_rng(3).bytes(1 << 20)

        async def h_fetch(conn, p):
            return rpc.RawPayload([memoryview(payload)])

        srv = rpc.RpcServer({"fetch": h_fetch}, name="chaos-srv",
                            auth_token=None, native=True)
        addr = await srv.start_tcp("127.0.0.1", 0)
        rpc.enable_link_chaos("chaos-cli/in_delay=0.05")
        conn = await rpc.connect(tuple(addr), auth_token=None,
                                 name="chaos-cli", native=True)
        try:
            import time
            dest = bytearray(len(payload))
            t0 = time.monotonic()
            n = await conn.call_raw("fetch", {}, memoryview(dest),
                                    timeout=60)
            dt = time.monotonic() - t0
            assert n == len(payload) and bytes(dest) == payload
            assert conn.io_stats["rx_takeovers"] == 0
            assert dt >= 0.05           # the plan was enforced
        finally:
            rpc.enable_link_chaos("")
            await conn.close()
            await srv.close()

    asyncio.run(main())


@needs_native
def test_raw_drop_cannot_desync_native_framing(clean_rpc):
    """An out_drop window swallowing whole header+payload groups (the
    PR-4 one-plan guard) must leave the native scanner frame-aligned:
    after the blackhole lifts, later transfers parse cleanly."""
    async def main():
        payload = np.random.default_rng(4).bytes(256 << 10)

        async def h_fetch(conn, p):
            return rpc.RawPayload([memoryview(payload)])

        srv = rpc.RpcServer({"fetch": h_fetch}, name="drop-srv",
                            auth_token=None, native=True)
        addr = await srv.start_tcp("127.0.0.1", 0)
        conn = await rpc.connect(tuple(addr), auth_token=None,
                                 name="drop-cli", native=True)
        try:
            dest = bytearray(len(payload))
            n = await conn.call_raw("fetch", {}, memoryview(dest),
                                    timeout=30)
            assert n == len(payload)
            # Blackhole our outbound for 0.4s: requests vanish whole.
            rpc.enable_link_chaos("drop-cli/out_drop=0:0.4")
            with pytest.raises((rpc.RpcError, asyncio.TimeoutError,
                                Exception)):
                await conn.call_raw("fetch", {}, memoryview(dest),
                                    timeout=0.3)
            await asyncio.sleep(0.3)
            rpc.enable_link_chaos("")
            dest2 = bytearray(len(payload))
            n = await conn.call_raw("fetch", {}, memoryview(dest2),
                                    timeout=30)
            assert n == len(payload) and bytes(dest2) == payload
        finally:
            rpc.enable_link_chaos("")
            await conn.close()
            await srv.close()

    asyncio.run(main())


# ------------------------------------------------------- syscall batching ---
@pytest.mark.parametrize("mode", ["native", "python"])
def test_one_wave_two_transport_submissions(mode):
    """A same-tick wave of K requests must leave in <= 2 transport
    submissions (the acceptance budget: syscalls per submit_batch wave
    <= 2); the native path additionally proves it used writev."""
    native = mode == "native"
    if native:
        _skip_without_native()

    async def main():
        def f_ping(conn, p):
            return p

        srv = rpc.RpcServer({}, fast_handlers={"ping": f_ping},
                            name="wave", auth_token=None, native=native)
        addr = await srv.start_tcp("127.0.0.1", 0)
        conn = await rpc.connect(tuple(addr), auth_token=None,
                                 native=native)
        try:
            await conn.call("ping", 0)          # auth + warm the path
            before = dict(conn.io_stats)
            futs = [asyncio.ensure_future(conn.call("ping", i))
                    for i in range(64)]
            out = await asyncio.gather(*futs)
            assert out == list(range(64))
            delta = conn.io_stats["tx_syscalls"] - before["tx_syscalls"]
            frames = conn.io_stats["tx_frames"] - before["tx_frames"]
            assert frames == 64
            assert delta <= 2, f"{delta} submissions for one wave"
            if native:
                assert conn.io_stats["tx_writev"] > before["tx_writev"]
            # call_many: one frame for the whole wave, one submission.
            before = dict(conn.io_stats)
            out = await asyncio.gather(
                *conn.call_many("ping", list(range(32))))
            assert out == list(range(32))
            assert conn.io_stats["tx_syscalls"] - before["tx_syscalls"] \
                <= 2
        finally:
            await conn.close()
            await srv.close()

    asyncio.run(main())


@needs_native
def test_send_raw_gathers_header_and_payload():
    """A raw reply (header + arena views) leaves the server through the
    vectored path — no per-buffer transport.write, pins dropped once the
    kernel owns the bytes."""
    async def main():
        a = np.arange(300_000, dtype=np.uint8)
        b = np.arange(200_000, dtype=np.uint8)[::-1].copy()
        released = []

        async def h_fetch(conn, p):
            return rpc.RawPayload(
                [memoryview(a), memoryview(b)],
                release=lambda: released.append(True))

        srv = rpc.RpcServer({"fetch": h_fetch}, name="gather",
                            auth_token=None, native=True)
        addr = await srv.start_tcp("127.0.0.1", 0)
        conn = await rpc.connect(tuple(addr), auth_token=None, native=True)
        try:
            dest = bytearray(a.nbytes + b.nbytes)
            n = await conn.call_raw("fetch", {}, memoryview(dest),
                                    timeout=30)
            assert n == len(dest)
            assert bytes(dest[:a.nbytes]) == a.tobytes()
            assert bytes(dest[a.nbytes:]) == b.tobytes()
            srv_conn = next(iter(srv.connections))
            assert srv_conn.io_stats["tx_writev"] >= 1
            await asyncio.sleep(0.05)
            assert released, "RawPayload release must run after send"
        finally:
            await conn.close()
            await srv.close()

    asyncio.run(main())


@needs_native
def test_oversize_payload_never_overruns_the_sink():
    """Memory safety: a peer announcing a raw payload LARGER than the
    registered sink must fail typed (like the pure-Python framer's
    scatter error), never engage the native recv takeover — a takeover
    here would recv() past the destination buffer."""
    import msgpack as _mp

    async def main():
        class EvilSrv(asyncio.Protocol):
            def connection_made(self, tr):
                self.tr = tr

            def data_received(self, data):
                unp = _mp.Unpacker(raw=False)
                unp.feed(data)
                for m in unp:
                    if isinstance(m, (list, tuple)) and len(m) >= 3 \
                            and isinstance(m[1], str) \
                            and m[1] != "__auth__":
                        big = 1 << 20
                        self.tr.write(_mp.packb(
                            [0, "__raw__", [m[0], big]],
                            use_bin_type=True))
                        self.tr.write(b"\xee" * big)

        loop = asyncio.get_running_loop()
        server = await loop.create_server(EvilSrv, "127.0.0.1", 0)
        addr = server.sockets[0].getsockname()[:2]
        conn = await rpc.connect(tuple(addr), auth_token=None, native=True)
        sink = bytearray(4096)
        with pytest.raises((rpc.RpcError, asyncio.TimeoutError)):
            await conn.call_raw("x", {}, memoryview(sink), timeout=10)
        assert conn.io_stats["rx_takeovers"] == 0
        await conn.close()
        server.close()

    asyncio.run(main())


@pytest.mark.parametrize("mode", ["native", "python"])
def test_non_minimal_raw_header_is_safe_under_both_framers(mode):
    """A peer packing the raw header in a legal-but-non-minimal msgpack
    encoding (str8 method name).  The Python framer decodes before
    matching, so it accepts and scatters normally; the native scanner
    matches the byte-exact minimal magic (wire invariant, see
    rpcframe.cc kMagic), so the header reaches frame dispatch — which
    must ABORT the connection typed rather than let the payload bytes
    desync the parser.  Both outcomes are safe; neither corrupts."""
    import msgpack as _mp
    if mode == "native":
        _skip_without_native()

    async def main():
        class NonMinimalSrv(asyncio.Protocol):
            def connection_made(self, tr):
                self.tr = tr

            def data_received(self, data):
                unp = _mp.Unpacker(raw=False)
                unp.feed(data)
                for m in unp:
                    if isinstance(m, (list, tuple)) and len(m) >= 3 \
                            and isinstance(m[1], str) \
                            and m[1] != "__auth__":
                        # Hand-packed header with str8 "__raw__" (the
                        # minimal form is fixstr): [0, "__raw__", [mid, 64]]
                        hdr = (b"\x93\x00" + b"\xd9\x07__raw__"
                               + _mp.packb([m[0], 64]))
                        self.tr.write(hdr + b"\xee" * 64)

        loop = asyncio.get_running_loop()
        server = await loop.create_server(NonMinimalSrv, "127.0.0.1", 0)
        addr = server.sockets[0].getsockname()[:2]
        conn = await rpc.connect(tuple(addr), auth_token=None,
                                 native=(mode == "native"))
        sink = bytearray(64)
        if mode == "python":
            # Decoded-object interception: works like a minimal header.
            n = await conn.call_raw("x", {}, memoryview(sink), timeout=5)
            assert n == 64 and bytes(sink) == b"\xee" * 64
        else:
            with pytest.raises((rpc.RpcError, asyncio.TimeoutError)):
                await conn.call_raw("x", {}, memoryview(sink), timeout=5)
            assert conn.closed      # aborted typed, not desynced
        await conn.close()
        server.close()

    asyncio.run(main())


def test_native_build_staleness_is_by_source_content(tmp_path, monkeypatch):
    """A copy of the tree promises nothing about mtimes: the binary is
    reused exactly while its source reads as it did at the last build,
    rebuilt when the content changes, and a failed build raises instead
    of loading a binary that no longer matches its source."""
    from ray_tpu._private import native_build
    src = tmp_path / "lib.cc"
    so = tmp_path / "_lib.so"
    src.write_text('extern "C" int answer() { return 1; }\n')
    calls = []

    def fake_gxx(cmd, **_kw):
        calls.append(cmd)
        with open(cmd[cmd.index("-o") + 1], "wb") as f:
            f.write(b"built from: " + src.read_bytes())

    monkeypatch.setattr(native_build.subprocess, "run", fake_gxx)
    assert native_build.build_so(str(src), str(so)) == str(so)
    assert len(calls) == 1
    # Same content, mtimes scrambled both ways: still current.
    os.utime(src, (2_000_000_000, 2_000_000_000))
    os.utime(so, (1, 1))
    native_build.build_so(str(src), str(so))
    assert len(calls) == 1
    # New content, source made to look OLDER than the binary: rebuilt.
    src.write_text('extern "C" int answer() { return 2; }\n')
    os.utime(src, (1, 1))
    native_build.build_so(str(src), str(so))
    assert len(calls) == 2 and b"return 2" in so.read_bytes()
    # New content and no compiler: raise, never keep the stale binary.
    src.write_text('extern "C" int answer() { return 3; }\n')
    monkeypatch.setattr(native_build.subprocess, "run",
                        lambda *a, **k: (_ for _ in ()).throw(
                            FileNotFoundError("g++ not found")))
    with pytest.raises(FileNotFoundError):
        native_build.build_so(str(src), str(so))


# ---------------------------------------------------------------- fallback --
def test_corrupt_extension_falls_back_to_python(tmp_path, caplog):
    """A corrupt/missing .so must degrade to the pure-Python framer with
    one warning — never crash, never half-enable."""
    from ray_tpu._private import native_build
    bad = tmp_path / "_rpcframe.so"
    bad.write_bytes(b"this is not an ELF")
    # Point the loader at garbage stamped as built from today's source.
    (tmp_path / "_rpcframe.so.sha256").write_text(
        native_build.source_digest(rpcframe._SRC))
    old_so, old_lib, old_failed = rpcframe._SO, rpcframe._lib, \
        rpcframe._failed
    try:
        rpcframe._reset_for_tests(str(bad))
        assert not rpcframe.available()
        assert not rpcframe.available()     # second call: no second try

        async def main():
            async def h_echo(conn, p):
                return p

            srv = rpc.RpcServer({"echo": h_echo}, name="fb",
                                auth_token=None)
            addr = await srv.start_tcp("127.0.0.1", 0)
            conn = await rpc.connect(tuple(addr), auth_token=None)
            try:
                assert not conn._use_native
                assert await conn.call("echo", {"x": 1}) == {"x": 1}
                dest = bytearray(100_000)
                srv.handlers["fetch"] = \
                    lambda c, p: rpc.RawPayload([b"q" * 100_000])
                n = await conn.call_raw("fetch", {}, memoryview(dest),
                                        timeout=10)
                assert n == 100_000 and dest[:2] == b"qq"
            finally:
                await conn.close()
                await srv.close()

        asyncio.run(main())
    finally:
        rpcframe._reset_for_tests(old_so)
        rpcframe._lib, rpcframe._failed = old_lib, old_failed


# -------------------------------------------------------------- copy audit --
@needs_native
def test_pull_copies_per_byte_pinned():
    """Native-path pull: ZERO intermediate copies per chunk (bytes go
    wire -> destination buffer); swarm partial serves: exactly one copy
    per byte (the unsealed buffer's lifetime belongs to the pull)."""
    from test_data_plane import CHUNK, _mini_agent

    async def main():
        data = bytes(range(256)) * 4096        # 1 MiB

        async def h_fetch(conn, p):
            off, ln = p["offset"], p["length"]
            return rpc.RawPayload([memoryview(data)[off:off + ln]])

        srv = rpc.RpcServer({"fetch_chunk": h_fetch}, name="src",
                            auth_token=None, native=True)
        addr = await srv.start_tcp("127.0.0.1", 0)
        peer = await rpc.connect(tuple(addr), auth_token=None, native=True)
        agent = _mini_agent()
        dest = bytearray(len(data))
        mv = memoryview(dest)
        before = rpc.copy_audit_snapshot()
        await agent._stream_chunks(
            [peer], b"o" * 20, len(data),
            make_sink=lambda pos, n: mv[pos:pos + n])
        after = rpc.copy_audit_snapshot()
        assert bytes(dest) == data
        for tag in ("pull_legacy_chunk", "pull_hedge_staging"):
            assert after.get(tag, 0) == before.get(tag, 0), tag
        await peer.close()
        await srv.close()

    asyncio.run(main())


def test_swarm_partial_serve_copies_exactly_once():
    from ray_tpu._private.agent import NodeAgent, _intervals_add

    async def main():
        agent = NodeAgent.__new__(NodeAgent)
        agent._bytes_served = 0
        agent.spilled = {}

        class _NoStore:
            def get(self, oid, timeout_ms=0):
                return None

        agent.store = _NoStore()
        buf = bytearray(b"S" * (64 << 10))
        part = {"size": len(buf), "buf": memoryview(buf), "done": []}
        _intervals_add(part["done"], 0, len(buf))
        agent._partial = {b"o" * 20: part}
        before = rpc.copy_audit_snapshot().get("serve_partial_chunk", 0)
        res = await agent.h_fetch_chunk(None, {
            "object_id": b"o" * 20, "offset": 0, "length": 64 << 10,
            "raw": True})
        assert isinstance(res, rpc.RawPayload) and res.nbytes == 64 << 10
        after = rpc.copy_audit_snapshot().get("serve_partial_chunk", 0)
        assert after - before == 64 << 10      # exactly 1 copy per byte
        res.close()

    asyncio.run(main())


# ------------------------------------------------------ mixed-mode cluster --
@needs_native
def test_mixed_mode_cluster_pull_and_submit_batch():
    """A node running the pure-Python framer joins a native cluster:
    bulk pull (native driver/agent -> python agent) and submit_batch
    task waves (native driver -> python node's workers) both roundtrip.
    This is the no-mixed-mode-crash acceptance test."""
    from ray_tpu._private import node as node_mod
    from ray_tpu._private import rpc as rpc_mod

    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=0)                   # tasks must go remote
    proc = None
    try:
        core = ray_tpu._core()
        proc, addr, _store, _nid = node_mod.start_agent(
            core.session_dir, core.gcs_address, {"CPU": 2.0},
            labels={"test": "python_framer_node"},
            store_capacity=64 << 20,
            system_config={"rpc_native_framer": False})

        # Bulk pull: 4 MiB object owned by the (native) driver, pulled
        # by the python-framer agent over chunked raw frames.
        payload = np.frombuffer(
            np.random.default_rng(9).bytes(4 << 20), dtype=np.uint8)
        ref = ray_tpu.put(payload)

        async def _pull():
            conn = await rpc_mod.connect(tuple(addr), name="drv->pyn",
                                         retries=30)
            try:
                ok = await conn.call("pull_object", {
                    "object_id": ref.binary(),
                    "from_addrs": [list(core.agent_address)],
                    "priority": 0}, timeout=120)
                assert ok, "mixed-mode pull failed"
            finally:
                await conn.close()

        asyncio.run_coroutine_threadsafe(_pull(), core.loop).result(150)

        # submit_batch wave onto the python-framer node's workers.
        @ray_tpu.remote
        def bump(i):
            return i + 1

        out = ray_tpu.get([bump.remote(i) for i in range(40)],
                          timeout=120)
        assert out == list(range(1, 41))
    finally:
        if proc is not None:
            proc.terminate()
            try:
                proc.wait(timeout=10)
            except Exception:
                proc.kill()
        ray_tpu.shutdown()
