"""Socket broker transport: framing, server/client parity, cross-process
round trips, reconnect, concurrency, and torn-write rejection."""
import multiprocessing as mp
import os
import socket
import struct
import threading
import time
import zlib

import pytest

# The test process has JAX's threads running; os.fork() under threads is
# what the RuntimeWarning warns about, so every subprocess here uses the
# spawn context and this marker turns any regression into a failure.
pytestmark = pytest.mark.filterwarnings(
    "error:os.fork\\(\\) was called:RuntimeWarning")

from repro.core import (Broker, Context, InMemoryPartitionLog, OffsetRange,
                        PartitionLog, StreamingContext)
from repro.data.transport import (MAGIC, BrokerServer, FrameError,
                                  RemoteBroker, TransportError, parse_address,
                                  recv_frame, send_frame, serve_broker)


# -- framing -----------------------------------------------------------------

def _pair():
    a, b = socket.socketpair()
    a.settimeout(5)
    b.settimeout(5)
    return a, b


def test_frame_roundtrip():
    a, b = _pair()
    for payload in (b"", b"x", os.urandom(70_000)):
        send_frame(a, payload)
        assert recv_frame(b) == payload
    a.close()
    assert recv_frame(b) is None          # clean EOF at a frame boundary
    b.close()


def test_torn_frame_rejected():
    a, b = _pair()
    header = struct.pack(">2sII", MAGIC, 100, zlib.crc32(b"irrelevant"))
    a.sendall(header + b"only-16-bytes!!!")    # promises 100, delivers 16
    a.close()
    with pytest.raises(FrameError, match="torn frame"):
        recv_frame(b)
    b.close()


def test_bad_magic_rejected():
    a, b = _pair()
    a.sendall(struct.pack(">2sII", b"ZZ", 4, 0) + b"data")
    with pytest.raises(FrameError, match="bad magic"):
        recv_frame(b)
    a.close(); b.close()


def test_checksum_mismatch_rejected():
    a, b = _pair()
    payload = b"detector-frame-bytes"
    header = struct.pack(">2sII", MAGIC, len(payload),
                         zlib.crc32(payload) ^ 0xDEAD)
    a.sendall(header + payload)
    with pytest.raises(FrameError, match="checksum"):
        recv_frame(b)
    a.close(); b.close()


def test_oversized_length_rejected_before_alloc():
    a, b = _pair()
    a.sendall(struct.pack(">2sII", MAGIC, 1 << 31, 0))
    with pytest.raises(FrameError, match="exceeds"):
        recv_frame(b)
    a.close(); b.close()


def test_parse_address():
    assert parse_address("10.0.0.7:9092") == ("10.0.0.7", 9092)
    assert parse_address(":9092") == ("127.0.0.1", 9092)
    assert parse_address("/tmp/broker.sock") == "/tmp/broker.sock"


# -- PartitionLog protocol extraction ---------------------------------------

class ListBackedLog:
    """Minimal alternate PartitionLog: proves Broker only needs the protocol."""

    def __init__(self):
        self.rows = []
        self._lock = threading.Lock()

    def append(self, key, value, timestamp):
        with self._lock:
            from repro.core.broker import Record
            self.rows.append(Record(key, value, len(self.rows), timestamp))
            return len(self.rows) - 1

    def read(self, start, until):
        with self._lock:
            return self.rows[start:min(until, len(self.rows))]

    def end_offset(self):
        with self._lock:
            return len(self.rows)


def test_partition_log_protocol():
    assert isinstance(InMemoryPartitionLog(), PartitionLog)
    assert isinstance(ListBackedLog(), PartitionLog)


def test_broker_over_custom_log_factory():
    b = Broker(log_factory=ListBackedLog)
    b.create_topic("t", 2)
    for i in range(6):
        b.produce("t", i, partition=i % 2)
    assert [r.value for r in b.read(OffsetRange("t", 0, 0, 9))] == [0, 2, 4]
    assert b.end_offsets("t") == [3, 3]


def test_broker_commit_monotonic_and_lag():
    b = Broker()
    b.create_topic("t", 2)
    for i in range(10):
        b.produce("t", i, partition=i % 2)
    assert b.lag("t") == 10
    b.commit("t", 0, 4)
    b.commit("t", 0, 2)                   # replay never rewinds progress
    b.commit("t", 1, 5)
    assert b.committed("t") == [4, 5]
    assert b.lag("t") == 1
    with pytest.raises(KeyError):
        b.commit("nope", 0, 1)


# -- server/client parity ----------------------------------------------------

@pytest.fixture
def served(tmp_path):
    broker = Broker()
    server = serve_broker(broker, str(tmp_path / "broker.sock"))
    client = RemoteBroker(server.address, max_retries=2, retry_delay=0.01)
    yield broker, server, client
    client.close()
    server.stop()


def test_remote_matches_local(served):
    broker, server, client = served
    assert client.ping()
    client.create_topic("t", 3)
    offs = [client.produce("t", {"i": i}, key=f"k{i}".encode(),
                           partition=i % 3) for i in range(9)]
    assert offs == [0, 0, 0, 1, 1, 1, 2, 2, 2]
    assert client.topics() == broker.topics() == ["t"]
    assert client.num_partitions("t") == 3
    assert client.end_offsets("t") == broker.end_offsets("t") == [3, 3, 3]
    assert client.end_offset("t", 1) == 3
    recs = client.read(OffsetRange("t", 1, 0, 10))
    assert [r.value for r in recs] == [{"i": 1}, {"i": 4}, {"i": 7}]
    assert [r.offset for r in recs] == [0, 1, 2]
    client.commit("t", 0, 3)
    assert broker.committed("t") == [3, 0, 0]
    assert client.lag("t") == broker.lag("t") == 6


def test_remote_raises_broker_errors(served):
    _, _, client = served
    with pytest.raises(KeyError):
        client.end_offsets("missing-topic")
    client.create_topic("t")
    with pytest.raises(ValueError):
        client.create_topic("t")
    assert client.ping()                  # connection survives error frames


def test_remote_numpy_payloads(served):
    np = pytest.importorskip("numpy")
    _, _, client = served
    client.create_topic("frames")
    frame = np.arange(64 * 64, dtype=np.float32).reshape(64, 64)
    client.produce("frames", (7, frame), key=b"frame-7")
    (rec,) = client.read(OffsetRange("frames", 0, 0, 1))
    idx, got = rec.value
    assert idx == 7 and got.dtype == np.float32
    np.testing.assert_array_equal(got, frame)


# -- cross-process round trip ------------------------------------------------

def _producer_main(address, n):
    from repro.data.transport import RemoteBroker
    client = RemoteBroker(address)
    for i in range(n):
        client.produce("xp", i, key=f"p{i}".encode(), partition=i % 2)
    client.close()


def test_append_read_across_processes(tmp_path):
    broker = Broker()
    broker.create_topic("xp", 2)
    server = serve_broker(broker, ("127.0.0.1", 0))
    try:
        proc = mp.get_context("spawn").Process(
            target=_producer_main, args=(server.address, 40))
        proc.start()
        proc.join(timeout=30)
        assert proc.exitcode == 0
        assert sum(broker.end_offsets("xp")) == 40
        evens = [r.value for r in broker.read(OffsetRange("xp", 0, 0, 99))]
        assert evens == list(range(0, 40, 2))   # per-partition total order
    finally:
        server.stop()


def test_streaming_consumer_over_remote_broker(tmp_path):
    """The consumer side of the split: StreamingContext driven entirely
    through RemoteBroker, commits landing on the served broker."""
    broker = Broker()
    server = serve_broker(broker, str(tmp_path / "b.sock"))
    client = RemoteBroker(server.address)
    try:
        client.create_topic("t", 2)
        for i in range(12):
            client.produce("t", i, partition=i % 2)
        sc = StreamingContext(Context(), client, max_records_per_partition=4)
        sc.subscribe(["t"])
        seen = []
        sc.foreach_batch(lambda rdd, info: seen.extend(rdd.collect()))
        sc.run_batches(3)
        assert sorted(seen) == list(range(12))
        assert broker.committed("t") == [6, 6]   # pushed over the wire
        assert client.lag("t") == 0
    finally:
        client.close()
        server.stop()


# -- reconnect ---------------------------------------------------------------

def test_client_reconnects_after_server_restart():
    broker = Broker()
    broker.create_topic("t")
    server = serve_broker(broker, ("127.0.0.1", 0))
    client = RemoteBroker(server.address, max_retries=6, retry_delay=0.05)
    assert client.produce("t", "before") == 0
    host, port = server.address
    server.stop()
    server2 = BrokerServer(broker, (host, port)).start()
    try:
        assert client.produce("t", "after") == 1      # transparent reconnect
        assert client.reconnects >= 1
        assert [r.value for r in broker.read(OffsetRange("t", 0, 0, 2))] == \
            ["before", "after"]
    finally:
        client.close()
        server2.stop()


def test_retries_are_bounded():
    client = RemoteBroker(("127.0.0.1", 1), connect_timeout=0.2,
                          max_retries=1, retry_delay=0.01)
    with pytest.raises(TransportError, match="unreachable after 2 attempts"):
        client.ping()


# -- concurrency -------------------------------------------------------------

def test_concurrent_producers_one_topic(tmp_path):
    broker = Broker()
    broker.create_topic("t", 1)
    server = serve_broker(broker, str(tmp_path / "b.sock"))
    n_producers, per_producer = 4, 50
    errors = []

    def producer(pid):
        try:
            client = RemoteBroker(server.address)
            for i in range(per_producer):
                client.produce("t", (pid, i), key=f"{pid}-{i}".encode())
            client.close()
        except Exception as e:            # pragma: no cover - failure path
            errors.append(e)

    threads = [threading.Thread(target=producer, args=(p,))
               for p in range(n_producers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    server.stop()
    assert not errors
    recs = broker.read(OffsetRange("t", 0, 0, 10 ** 6))
    assert len(recs) == n_producers * per_producer
    assert [r.offset for r in recs] == list(range(len(recs)))  # dense log
    for p in range(n_producers):          # each producer's order preserved
        assert [v for pid, v in (r.value for r in recs) if pid == p] == \
            list(range(per_producer))


# -- torn writes against a live server --------------------------------------

def test_server_rejects_garbage_and_survives(served):
    _, server, client = served
    client.create_topic("t")
    client.produce("t", 1)
    # a rogue/corrupt peer: valid header promising more bytes than sent
    rogue = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    rogue.connect(server.address)
    rogue.sendall(struct.pack(">2sII", MAGIC, 500, 0) + b"short")
    rogue.close()
    # and one speaking a different protocol entirely
    rogue2 = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    rogue2.connect(server.address)
    rogue2.sendall(b"GET / HTTP/1.1\r\n\r\n")
    rogue2.close()
    deadline = time.monotonic() + 5
    while server.frames_rejected < 2 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert server.frames_rejected == 2
    assert client.produce("t", 2) == 1    # healthy clients unaffected
    assert client.end_offsets("t") == [2]


# -- hardening from review ---------------------------------------------------

def test_wire_unpickler_refuses_dangerous_globals(served):
    """A well-formed frame whose pickle smuggles a callable must be refused
    before instantiation — the server answers with an error, runs nothing."""
    import pickle

    from repro.data.transport import KIND_PICKLE, decode_message

    evil = KIND_PICKLE + pickle.dumps((os.system, ("echo pwned",)))
    with pytest.raises(FrameError, match="refusing to unpickle"):
        decode_message(evil)

    _, server, client = served
    rogue = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    rogue.settimeout(5)
    rogue.connect(server.address)
    send_frame(rogue, evil)
    resp = recv_frame(rogue)
    rogue.close()
    status, exc_name, message = decode_message(resp)
    assert status == "err" and "refusing to unpickle" in message
    assert client.ping()                  # server healthy, nothing executed


def test_oversized_request_fails_fast_no_retries(served, monkeypatch):
    import repro.data.transport as tr
    _, _, client = served
    client.create_topic("t")
    monkeypatch.setattr(tr, "MAX_FRAME_BYTES", 1024)
    with pytest.raises(FrameError, match="exceeds"):
        client.produce("t", b"x" * 4096)
    assert client.reconnects == 0         # rejected before any send/retry
    monkeypatch.undo()
    assert client.produce("t", b"small") == 0


def test_commit_rejects_bad_partition_and_offset(served):
    broker, _, client = served
    client.create_topic("t", 2)
    client.produce("t", 1, partition=0)
    for bad in [("t", -1, 1), ("t", 2, 1), ("t", 0, -1), ("t", 0, 5)]:
        with pytest.raises(ValueError):
            client.commit(*bad)
    assert broker.committed("t") == [0, 0]   # nothing poisoned
    client.commit("t", 0, 1)
    assert broker.committed("t") == [1, 0]


# -- batched produce over the wire -------------------------------------------

def test_ingest_batches_produce_over_remote(tmp_path):
    """IngestRunner's flush buffer amortizes the socket: ~1 produce_many per
    (partition, flush) instead of one round trip per record, with nothing
    lost and per-partition order intact."""
    from repro.data import IngestConfig, IngestRunner, SyntheticRateSource

    broker = Broker()
    server = serve_broker(broker, str(tmp_path / "b.sock"))
    client = RemoteBroker(server.address)
    try:
        runner = IngestRunner(client)
        m = runner.add(SyntheticRateSource(rate=1e9, total=500),
                       IngestConfig(topic="t", partitions=2, poll_batch=100,
                                    flush_records=100, max_pending=1 << 30))
        runner.run_inline(timeout=60)
        assert runner.done
        assert m.produced == 500
        assert sum(broker.end_offsets("t")) == 500
        # 5 polls x 100 records -> 5 flushes x 2 partition groups
        assert m.produce_calls <= 10
        for p in range(2):                 # round-robin kept per-part order
            vals = [r.value for r in broker.read(OffsetRange("t", p, 0, 999))]
            assert vals == list(range(p, 500, 2))
    finally:
        client.close()
        server.stop()


@pytest.mark.parametrize("flush_records", [1, 50])
def test_remote_ingest_with_server_side_lag_delivers_every_record(
        tmp_path, flush_records):
    """The split deployment: IngestRunner produces through RemoteBroker and
    reads its backpressure lag from the served broker, where a local
    StreamingContext commits. Per-record and batched produce both deliver
    every record once, and the commits reach the log's end."""
    from repro.data import IngestConfig, IngestRunner, SyntheticRateSource

    broker = Broker()
    server = serve_broker(broker, str(tmp_path / "b.sock"))
    client = RemoteBroker(server.address)
    try:
        runner = IngestRunner(client, consumer=client)
        m = runner.add(SyntheticRateSource(rate=1e9, total=300),
                       IngestConfig(topic="t", partitions=2, poll_batch=50,
                                    max_pending=200,
                                    flush_records=flush_records))
        sc = StreamingContext(Context(), broker, max_records_per_partition=25)
        sc.subscribe(["t"])
        got = []
        sc.foreach_batch(lambda rdd, info: got.extend(rdd.collect()))
        ticks = 0
        while not runner.done or sc.lag("t") > 0:
            runner.pump()
            sc.run_one_batch()
            ticks += 1
            assert ticks < 1000, "pipeline never drained"
        assert m.produced == 300 and m.dropped == 0
        assert sorted(got) == list(range(300))
        assert broker.committed("t") == broker.end_offsets("t")
        assert client.lag("t") == 0
    finally:
        client.close()
        server.stop()


@pytest.mark.parametrize("array_frames", [True, False])
def test_ingest_ndarray_payloads_over_remote(tmp_path, monkeypatch,
                                             array_frames):
    """Detector-style ndarray values, produced in batches through the
    socket, arrive whole and exact whether they ride zero-copy 'A' frames
    or the pickled 'P' fallback."""
    np = pytest.importorskip("numpy")
    import repro.data.transport as tr
    from repro.data import IngestConfig, IngestRunner, SyntheticRateSource

    monkeypatch.setattr(tr, "USE_ARRAY_FRAMES", array_frames)
    frame = np.random.default_rng(0).standard_normal(
        (32, 32)).astype(np.float32)
    broker = Broker()
    server = serve_broker(broker, str(tmp_path / "b.sock"))
    client = RemoteBroker(server.address, shm=False)
    try:
        runner = IngestRunner(client)
        m = runner.add(SyntheticRateSource(rate=1e9, total=40,
                                           value_fn=lambda i: (i, frame * i)),
                       IngestConfig(topic="t", partitions=2, poll_batch=10,
                                    flush_records=10, max_pending=1 << 30))
        runner.run_inline(timeout=60)
        assert m.produced == 40
        assert sum(broker.end_offsets("t")) == 40
        seen = []
        for p in range(2):
            for rec in broker.read(OffsetRange("t", p, 0, 999)):
                i, got = rec.value
                np.testing.assert_array_equal(got, frame * i)
                seen.append(i)
        assert sorted(seen) == list(range(40))
    finally:
        client.close()
        server.stop()


def test_ingest_flush_deadline_and_done():
    """A partially-filled buffer flushes when the oldest record ages past
    flush_interval, and done stays False until the buffer drains."""
    from repro.data import IngestConfig, IngestRunner

    class Trickle:
        def __init__(self):
            self.sent = False
            self.exhausted = False

        def poll(self, max_records):
            if not self.sent:
                self.sent = True
                return [(b"k", "only-record")]
            return []

    broker = Broker()
    runner = IngestRunner(broker)
    source = Trickle()
    m = runner.add(source, IngestConfig(topic="t", flush_records=1000,
                                        flush_interval=0.05))
    runner.pump()
    assert broker.end_offsets("t") == [0]  # buffered, not yet produced
    assert m.produced == 0 and not runner.done
    time.sleep(0.06)
    runner.pump()                          # deadline flush
    assert broker.end_offsets("t") == [1]
    assert m.produced == 1
    source.exhausted = True
    assert runner.done


# -- shared-memory 'S' frames end to end -------------------------------------

def _shm_leftovers() -> list[str]:
    """Segments created by this process's servers still visible in /dev/shm
    (the pool names embed the creator pid, so other processes never alias)."""
    prefix = f"reproshm_{os.getpid()}_"
    try:
        return [f for f in os.listdir("/dev/shm") if f.startswith(prefix)]
    except FileNotFoundError:             # pragma: no cover - non-Linux
        return []


def _wait_no_shm_leftovers(timeout: float = 5.0) -> list[str]:
    deadline = time.monotonic() + timeout
    leftovers = _shm_leftovers()
    while leftovers and time.monotonic() < deadline:
        time.sleep(0.02)
        leftovers = _shm_leftovers()
    return leftovers


def test_shm_negotiated_same_host_end_to_end(served):
    """A same-host UDS client negotiates shm in hello; array-bearing
    produces ride 'S' frames (bulk bytes never on the socket), reads are
    exact, and closing the connection strands nothing in /dev/shm."""
    np = pytest.importorskip("numpy")
    broker, server, client = served
    client.create_topic("frames")
    arrs = [np.arange(i, i + 64 * 64, dtype=np.float32).reshape(64, 64)
            for i in range(6)]
    for i, a in enumerate(arrs):
        client.produce("frames", (i, a), key=f"f{i}".encode())
    assert client.shm_frames_sent == 6
    assert server.shm_frames == 6
    assert server.stats()["shm_segments"] >= 1
    recs = client.read(OffsetRange("frames", 0, 0, 10))
    for i, rec in enumerate(recs):
        idx, got = rec.value
        assert idx == i
        np.testing.assert_array_equal(got, arrs[i])
    client.close()
    assert _wait_no_shm_leftovers() == []


def test_shm_kill_switch_fallback_parity(served, monkeypatch):
    """USE_SHM_FRAMES=False (and shm=False per client) falls back to plain
    'A' frames with identical results — the kill switch is pure mechanism."""
    import numpy as np

    import repro.data.transport as tr

    broker, server, _ = served
    arr = np.arange(128 * 128, dtype=np.float32).reshape(128, 128)

    monkeypatch.setattr(tr, "USE_SHM_FRAMES", False)
    off = RemoteBroker(server.address)
    off.create_topic("t")
    off.produce("t", (0, arr))
    assert off.shm_frames_sent == 0 and server.shm_frames == 0
    off.close()

    monkeypatch.undo()
    optout = RemoteBroker(server.address, shm=False)   # per-client opt-out
    optout.produce("t", (1, arr))
    assert optout.shm_frames_sent == 0 and server.shm_frames == 0
    optout.close()

    on = RemoteBroker(server.address)
    on.produce("t", (2, arr))
    assert on.shm_frames_sent == 1 and server.shm_frames == 1
    recs = on.read(OffsetRange("t", 0, 0, 10))
    on.close()
    assert [r.value[0] for r in recs] == [0, 1, 2]
    for _, got in (r.value for r in recs):             # all three paths equal
        np.testing.assert_array_equal(got, arr)
    assert _wait_no_shm_leftovers() == []


def test_attach_segment_never_touches_resource_tracker(monkeypatch):
    """Attaching a server-owned segment must neither register nor
    unregister it with this process's resource_tracker: a producer spawned
    via ``multiprocessing`` *shares* the server's tracker, so either call
    unbalances the server's own create/unlink pair and the shared tracker
    dies with a KeyError traceback when the server unlinks (regression:
    ``examples/remote_ingest.py`` printed exactly that)."""
    from multiprocessing import resource_tracker, shared_memory

    from repro.data.transport import _attach_untracked, _close_shm

    seg = shared_memory.SharedMemory(
        create=True, size=4096, name=f"reproshm_{os.getpid()}_attachtest")
    calls: list[tuple] = []
    try:
        monkeypatch.setattr(resource_tracker, "register",
                            lambda n, t: calls.append(("register", n, t)))
        monkeypatch.setattr(resource_tracker, "unregister",
                            lambda n, t: calls.append(("unregister", n, t)))
        shm = _attach_untracked(seg.name)
        assert shm.buf is not None and shm.size >= 4096
        _close_shm(shm)
        observed = list(calls)
        # the patched register must be restored, not left swallowing
        assert resource_tracker.register.__name__ == "<lambda>"
    finally:
        monkeypatch.undo()
        seg.close()
        seg.unlink()
    assert observed == []


def test_shm_hello_refuses_foreign_host(served):
    """A hello claiming a different host token is denied shm (descriptors
    would name segments the peer cannot map)."""
    from repro.data.transport import decode_message, send_message

    _, server, _ = served
    sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    sock.settimeout(5)
    sock.connect(server.address)
    try:
        send_message(sock, ("hello", ({"host": "elsewhere:0000",
                                       "shm": True},), {}))
        status, caps = decode_message(recv_frame(sock))
        assert status == "ok" and caps["shm"] is False
        # and an shm_alloc on the un-negotiated connection declines cleanly
        send_message(sock, ("shm_alloc", (1024,), {}))
        assert decode_message(recv_frame(sock)) == ("ok", None)
    finally:
        sock.close()


_CHAOS_PRODUCER = r"""
import sys
import numpy as np
from repro.data.transport import RemoteBroker

client = RemoteBroker(sys.argv[1])
client.create_topic("chaos")
frame = np.ones((256, 256), dtype=np.float32)
client.produce("chaos", (0, frame))
print("READY", client.shm_frames_sent, flush=True)
while True:
    client.produce("chaos", (0, frame))
"""


def test_sigkill_mid_produce_leaves_no_shm(tmp_path):
    """Chaos pin for the server-owned-segments design: SIGKILL a producer
    mid-stream — the server unlinks every segment the connection leased
    (nothing stranded in /dev/shm) and the dead producer's resource_tracker
    has nothing to complain about (attached segments were unregistered)."""
    import signal
    import subprocess
    import sys

    broker = Broker()
    server = serve_broker(broker, str(tmp_path / "chaos.sock"))
    try:
        proc = subprocess.Popen(
            [sys.executable, "-c", _CHAOS_PRODUCER, server.address],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        try:
            line = proc.stdout.readline()
            assert line.split() == ["READY", "1"], \
                f"producer never negotiated shm: {line!r}"
            assert _shm_leftovers()        # segments live while it streams
            os.kill(proc.pid, signal.SIGKILL)
            proc.wait(timeout=10)
            # EOF arrives only once the producer's resource_tracker (the
            # last writer on the inherited pipe) has exited too — so this
            # read observes any leak warning it would ever print
            stderr = proc.stderr.read()
        finally:
            proc.stdout.close()
            proc.stderr.close()
        assert "resource_tracker" not in stderr, stderr
        assert "leaked" not in stderr, stderr
        assert _wait_no_shm_leftovers() == []
        assert server.stats()["shm_segments"] == 0
        assert broker.end_offsets("chaos")[0] >= 1   # it did stream for real
    finally:
        server.stop()


def test_ingest_add_tolerates_create_race():
    """Two producers' check-then-create on one topic must not kill the
    loser (the topic appearing between topics() and create_topic)."""
    from repro.data import IngestConfig, IngestRunner, SyntheticRateSource

    class RacyBroker(Broker):
        def topics(self):
            return []                     # always claims the topic is absent

    broker = RacyBroker()
    runner = IngestRunner(broker)
    runner.add(SyntheticRateSource(rate=1e9, total=1), IngestConfig(topic="t"))
    runner.add(SyntheticRateSource(rate=1e9, total=1), IngestConfig(topic="t"))
    assert Broker.topics(broker) == ["t"]


# -- op allow-list parity ----------------------------------------------------

def test_op_allowlist_parity():
    """Runtime complement to the static `transport-op-parity` rule: the
    _OPS allow-list, the server dispatch, and RemoteBroker's public
    surface must describe the same protocol — checked against the live
    objects, so ops built or decorated dynamically still count."""
    import inspect

    from repro.data import transport as t

    # ops the transport itself answers without touching the broker
    server_local = {"ping", "stats", "hello", "shm_alloc"}
    # connection internals issued by _connect/_send_shm, not a public method
    connection_internal = {"hello", "shm_alloc"}

    # every broker-bound op in _OPS is a real callable on Broker — the
    # server's getattr(self.broker, op) can never fall over
    for op in sorted(t._OPS - server_local):
        assert callable(getattr(t.Broker, op, None)), (
            f"allow-listed op {op!r} is not a Broker method")

    # drive every public RemoteBroker method against a recording stub and
    # diff the ops it issues against the allow-list
    rb = t.RemoteBroker.__new__(t.RemoteBroker)
    issued: set[str] = set()
    rb._request = lambda op, *a, **k: issued.add(op)

    dummy = {"rng": t.OffsetRange("t", 0, 0, 0), "pairs": [],
             "topics": ["t"], "cursors": {}, "hwms": {}}

    def arg_for(param):
        if param.name in dummy:
            return dummy[param.name]
        if param.annotation in (int, "int"):
            return 0
        return "x"

    public = [name for name, fn in vars(t.RemoteBroker).items()
              if inspect.isfunction(fn) and not name.startswith("_")
              and name != "close"]
    for name in public:
        fn = getattr(rb, name)
        sig = inspect.signature(fn)
        args = [arg_for(p) for p in sig.parameters.values()
                if p.default is inspect.Parameter.empty
                and p.kind in (p.POSITIONAL_ONLY,
                               p.POSITIONAL_OR_KEYWORD)]
        fn(*args)

    unlisted = issued - t._OPS
    assert not unlisted, f"RemoteBroker issues ops outside _OPS: {unlisted}"
    uncovered = t._OPS - issued - connection_internal
    assert not uncovered, (
        f"allow-listed ops with no public RemoteBroker issuer: {uncovered}")
