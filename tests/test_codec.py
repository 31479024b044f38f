"""Per-topic payload codecs (repro.data.codec): round trips, lossy bounds,
JAX parity, unknown-codec refusal, and end-to-end encode-at-flush /
decode-at-subscribe — including byte-identity replication of codec'd logs.
"""
import os

import numpy as np
import pytest

from repro.core import Broker, Context, OffsetRange, StreamingContext
from repro.data.codec import (SENTINEL, CodecBroker, UnknownCodecError,
                              codec_names, compose_decoder, get_codec,
                              maybe_decode)


# -- round trips -------------------------------------------------------------

_VALUES = [
    7,
    "text",
    b"raw-bytes",
    None,
    {"i": 1, "nested": (2.5, [b"x", None])},
    (3, np.arange(12, dtype=np.float32).reshape(3, 4)),
    {"frame": np.ones((4, 4), dtype=np.float64),
     "meta": {"idx": [np.arange(5, dtype=np.int64)]}},
]


def _eq(a, b) -> bool:
    if isinstance(a, np.ndarray) or isinstance(b, np.ndarray):
        return (isinstance(a, np.ndarray) and isinstance(b, np.ndarray)
                and a.dtype == b.dtype and a.shape == b.shape
                and np.array_equal(a, b))
    if isinstance(a, (list, tuple)) and isinstance(b, (list, tuple)):
        return (type(a) is type(b) and len(a) == len(b)
                and all(_eq(x, y) for x, y in zip(a, b)))
    if isinstance(a, dict) and isinstance(b, dict):
        return a.keys() == b.keys() and all(_eq(a[k], b[k]) for k in a)
    return type(a) is type(b) and a == b


def test_registry_names():
    assert codec_names() == ["int8", "raw", "zlib"]


@pytest.mark.parametrize("name", ["raw", "zlib"])
def test_lossless_roundtrip(name):
    codec = get_codec(name)
    for value in _VALUES:
        assert _eq(maybe_decode(codec.encode(value)), value)


def test_int8_roundtrip_structure_and_integers_exact():
    """int8 is lossy only on float arrays: structure, scalars, and integer
    arrays come back exact; float arrays come back same dtype/shape."""
    codec = get_codec("int8")
    value = {"frame": np.linspace(-1, 1, 16, dtype=np.float32).reshape(4, 4),
             "idx": np.arange(6, dtype=np.int32), "n": 3, "tag": "t"}
    got = maybe_decode(codec.encode(value))
    assert got.keys() == value.keys()
    assert np.array_equal(got["idx"], value["idx"])   # integers untouched
    assert got["n"] == 3 and got["tag"] == "t"
    assert got["frame"].dtype == np.float32
    assert got["frame"].shape == (4, 4)


def test_int8_lossy_error_bound():
    """Per-element error is bounded by the tensor's amax/127 (the documented
    contract), across dtypes and value ranges."""
    rng = np.random.default_rng(0)
    for dtype in (np.float32, np.float64):
        for scale_up in (1e-3, 1.0, 1e4):
            arr = (rng.standard_normal((64, 64)) * scale_up).astype(dtype)
            got = maybe_decode(get_codec("int8").encode(arr))
            bound = float(np.max(np.abs(arr))) / 127.0 + 1e-9
            assert float(np.max(np.abs(got.astype(np.float64)
                                       - arr.astype(np.float64)))) <= bound


def test_int8_empty_and_zero_arrays():
    for arr in (np.zeros((3, 3), dtype=np.float32),
                np.zeros((0,), dtype=np.float32)):
        got = maybe_decode(get_codec("int8").encode(arr))
        assert got.shape == arr.shape and np.array_equal(got, arr)


def test_int8_matches_jax_reference():
    """The NumPy quantizer is the codec-side mirror of the optimizer's JAX
    ``quantize_int8`` — identical q arrays and scales, so a value compressed
    for the wire degrades exactly like a gradient compressed for all-reduce."""
    jax = pytest.importorskip("jax")
    from repro.data.codec import _quantize
    from repro.optim.compression import dequantize_int8, quantize_int8

    rng = np.random.default_rng(1)
    for x in (rng.standard_normal((32, 32)).astype(np.float32),
              np.zeros((8,), dtype=np.float32),
              (rng.standard_normal(100) * 1e-6).astype(np.float32)):
        node = _quantize(x)
        q_jax, scale_jax = quantize_int8(jax.numpy.asarray(x))
        np.testing.assert_array_equal(node["q"], np.asarray(q_jax))
        assert node["s"] == pytest.approx(float(scale_jax), rel=1e-6)
        np.testing.assert_allclose(
            node["q"].astype(np.float32) * node["s"],
            np.asarray(dequantize_int8(q_jax, scale_jax)), rtol=1e-6)


# -- refusal and escaping ----------------------------------------------------

def test_unknown_codec_refused_everywhere():
    with pytest.raises(UnknownCodecError, match="martian"):
        get_codec("martian")
    with pytest.raises(UnknownCodecError):
        maybe_decode({SENTINEL: "martian", "v": 1})   # never passed through
    with pytest.raises(UnknownCodecError):
        Broker().create_topic("t", codec="martian")   # fails at create...


def test_unknown_codec_refused_over_the_wire(tmp_path):
    """...and the same error type crosses the transport from a remote
    create_topic (registered in the transport's typed-error table)."""
    from repro.data.transport import RemoteBroker, serve_broker

    server = serve_broker(Broker(), str(tmp_path / "b.sock"))
    client = RemoteBroker(server.address)
    try:
        with pytest.raises(UnknownCodecError):
            client.create_topic("t", codec="martian")
        client.create_topic("t", codec="int8")        # good names still work
        assert client.topic_codec("t") == "int8"
        with pytest.raises(KeyError):
            client.topic_codec("missing")
    finally:
        client.close()
        server.stop()


def test_sentinel_collision_escaped():
    """A *user* value that happens to be a dict carrying the sentinel key
    round-trips exactly through the raw codec's escape hatch instead of
    being misread as an encoded payload."""
    tricky = {SENTINEL: "zlib", "z": b"not really compressed"}
    wrapped = get_codec("raw").encode(tricky)
    assert wrapped is not tricky                      # escaped, not aliased
    assert _eq(maybe_decode(wrapped), tricky)
    # non-colliding values pass through the raw codec untouched (no wrap)
    plain = {"k": 1}
    assert get_codec("raw").encode(plain) is plain


def test_compose_decoder_order():
    """Codec decode runs first, then the user's value decoder."""
    codec = get_codec("zlib")
    dec = compose_decoder(lambda v: v * 10)
    assert dec(codec.encode(7)) == 70
    assert dec(3) == 30                               # unwrapped passthrough
    assert compose_decoder(None)(codec.encode("x")) == "x"


# -- topic configuration and the encode/decode boundary ----------------------

def test_topic_codec_configuration():
    b = Broker()
    b.create_topic("detector", codec="int8")
    b.create_topic("control")
    assert b.topic_codec("detector") == "int8"
    assert b.topic_codec("control") is None
    with pytest.raises(KeyError):
        b.topic_codec("missing")


def test_ingest_encodes_subscribe_decodes():
    """The full boundary: IngestRunner encodes at flush (values travel
    wrapped through the broker), StreamingContext decodes at subscribe —
    detector frames arrive as float arrays within the lossy bound."""
    from repro.data import IngestConfig, IngestRunner, SequenceSource

    class Frames(SequenceSource):
        def __init__(self, n):
            super().__init__()
            rng = np.random.default_rng(2)
            self.frames = [rng.standard_normal((8, 8)).astype(np.float32)
                           for _ in range(n)]

        def __len__(self):
            return len(self.frames)

        def record_at(self, i):
            return f"f{i}".encode(), (i, self.frames[i])

    broker = Broker()
    runner = IngestRunner(broker)
    source = Frames(6)
    m = runner.add(source, IngestConfig(topic="det", codec="int8",
                                        flush_records=3))
    runner.run_inline(timeout=30)
    assert m.produced == 6
    # on the log the values are wrapped (what durable segments would hold)
    raw = broker.read(OffsetRange("det", 0, 0, 10))
    assert all(isinstance(r.value, dict) and r.value[SENTINEL] == "int8"
               for r in raw)
    # a subscriber sees decoded values
    sc = StreamingContext(Context(), broker, max_records_per_partition=10)
    sc.subscribe(["det"])
    seen = []
    sc.foreach_batch(lambda rdd, info: seen.extend(rdd.collect()))
    sc.run_batches(1)
    assert len(seen) == 6
    for i, frame in sorted(seen):
        ref = source.frames[i]
        assert frame.dtype == np.float32
        assert np.max(np.abs(frame - ref)) <= np.max(np.abs(ref)) / 127 + 1e-9


def test_ingest_codec_values_cross_the_socket_encoded(tmp_path):
    """Encode-at-flush through the socket transport: every record lands on
    the served broker's log still codec-tagged, none lost, and decodes
    within the int8 bound."""
    from repro.data import IngestConfig, IngestRunner, SyntheticRateSource
    from repro.data.transport import RemoteBroker, serve_broker

    frame = np.random.default_rng(0).standard_normal(
        (16, 16)).astype(np.float32)
    broker = Broker()
    server = serve_broker(broker, str(tmp_path / "b.sock"))
    client = RemoteBroker(server.address, shm=False)
    try:
        runner = IngestRunner(client)
        m = runner.add(SyntheticRateSource(rate=1e9, total=48,
                                           value_fn=lambda i: (i, frame * i)),
                       IngestConfig(topic="t", partitions=1, poll_batch=16,
                                    flush_records=16, max_pending=1 << 30,
                                    codec="int8"))
        runner.run_inline(timeout=60)
        assert m.produced == 48
        assert broker.end_offsets("t") == [48]
        raw = broker.read(OffsetRange("t", 0, 0, 48))
        assert all(isinstance(r.value, dict) and r.value[SENTINEL] == "int8"
                   for r in raw)
        for rec in raw:
            i, got = maybe_decode(rec.value)
            ref = frame * i
            bound = np.max(np.abs(ref)) / 127 + 1e-6
            assert np.max(np.abs(got - ref)) <= bound
    finally:
        client.close()
        server.stop()


def test_topic_source_decodes_for_chained_stages():
    from repro.data import TopicSource

    broker = Broker()
    broker.create_topic("stage1", codec="zlib")
    codec = get_codec("zlib")
    broker.produce("stage1", codec.encode({"x": 1}), key=b"a")
    broker.produce("stage1", codec.encode((2, np.arange(3))), key=b"b")
    got = TopicSource(broker, "stage1", stop_at_end=True).poll(10)
    assert _eq(got[0], (b"a", {"x": 1}))
    assert _eq(got[1], (b"b", (2, np.arange(3))))


def test_codec_metrics_account_reduction():
    """ingest_codec_bytes_in/out: int8 on float32 frames shows ~4x fewer
    bytes leaving the encode boundary than entering it."""
    from repro.data import IngestConfig, IngestRunner, SequenceSource
    from repro.data.metrics import MetricsRegistry, set_registry

    class Frames(SequenceSource):
        def __len__(self):
            return 4

        def record_at(self, i):
            return None, np.ones((64, 64), dtype=np.float32)

    reg = MetricsRegistry()
    prev = set_registry(reg)
    try:
        runner = IngestRunner(Broker())
        runner.add(Frames(), IngestConfig(topic="t", codec="int8",
                                          flush_records=2))
        runner.run_inline(timeout=30)
        values = {m.name: m.value() for m in reg.metrics()}
        bytes_in = values["ingest_codec_bytes_in"]
        bytes_out = values["ingest_codec_bytes_out"]
        assert bytes_in >= 4 * 64 * 64 * 4
        assert bytes_out < bytes_in / 3           # ~4x minus wrapper overhead
    finally:
        set_registry(prev)


# -- composition with durability and replication -----------------------------

def test_replication_byte_identity_with_codec(tmp_path):
    """Codec'd payloads are opaque to the broker: a follower replicating a
    compressed topic lands byte-identical segment files, and decoding the
    replica yields the original values."""
    from repro.core.broker import COMMIT_TOPIC
    from repro.data.durable_log import DurableLogFactory
    from repro.data.replication import ReplicaFollower
    from repro.data.transport import serve_broker

    primary = Broker(log_factory=DurableLogFactory(str(tmp_path / "primary")),
                     commit_topic=COMMIT_TOPIC)
    server = serve_broker(primary, str(tmp_path / "p.sock"))
    primary.create_topic("t", 2, codec="zlib")
    codec = get_codec("zlib")
    values = [{"i": i, "blob": b"x" * 200} for i in range(30)]
    primary.produce_many("t", [(f"k{i}".encode(), codec.encode(v))
                               for i, v in enumerate(values)])
    fol = ReplicaFollower(server.address, str(tmp_path / "replica"))
    try:
        while fol.sync_once():
            pass
        assert fol.broker.end_offsets("t") == primary.end_offsets("t")
        for p in range(2):
            pdir = tmp_path / "primary" / "t" / f"p{p:04d}"
            fdir = tmp_path / "replica" / "t" / f"p{p:04d}"
            segs = sorted(f for f in os.listdir(pdir) if f.endswith(".seg"))
            assert segs == sorted(f for f in os.listdir(fdir)
                                  if f.endswith(".seg"))
            for seg in segs:
                assert (pdir / seg).read_bytes() == (fdir / seg).read_bytes()
        # the replica's records decode to the original values
        got = []
        for p in range(2):
            end = fol.broker.end_offset("t", p)
            got += [maybe_decode(r.value)
                    for r in fol.broker.read(OffsetRange("t", p, 0, end))]
        assert sorted(v["i"] for v in got) == list(range(30))
    finally:
        fol.stop()
        server.stop()


def test_codec_broker_adapter_passthrough():
    """CodecBroker: encode on produce, decode on read, everything else
    passes through — observationally identical with a lossless codec."""
    cb = CodecBroker(Broker(), codec="zlib")
    cb.create_topic("t", 2)
    assert cb.num_partitions("t") == 2
    cb.produce("t", {"a": 1}, partition=0)
    cb.produce_many("t", [(b"k", np.arange(4))], partition=0)
    recs = cb.read(OffsetRange("t", 0, 0, 10))
    assert _eq(recs[0].value, {"a": 1})
    assert _eq(recs[1].value, np.arange(4))
    # the wrapped broker holds encoded values
    inner = cb._broker.read(OffsetRange("t", 0, 0, 10))
    assert all(isinstance(r.value, dict) and r.value[SENTINEL] == "zlib"
               for r in inner)
