"""Wire-protocol codec: frame round trips, transactional rejection of
malformed frames (truncated / corrupt / oversize / interleaved), and the
payload codecs shared with the signal-shard schema.

Everything here is pure bytes + numpy — no sockets, no jit — so the
whole file runs in the fast tier.  The live socket/subprocess protocol
is exercised in ``test_fleet.py``.
"""
import zlib

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core.signals import SignalBatch
from repro.fleet import wire
from repro.fleet.wire import (FRAME_NAMES, FT_BYE, FT_DRAFT, FT_HELLO,
                              FT_SIGNALS, HEADER, MAX_PAYLOAD, WIRE_VERSION,
                              FrameReader, WireError, decode_draft,
                              decode_json, decode_npz, decode_signals,
                              draft_payload, encode_frame, json_payload,
                              signals_payload)


def _drain(reader, data):
    return list(reader.feed(data))


# ------------------------------------------------------------ round trips
def test_frame_roundtrip_all_types_and_empty_payload():
    reader = FrameReader()
    frames = []
    for ftype in FRAME_NAMES:
        payload = b"" if ftype == FT_BYE else bytes([ftype]) * (7 * ftype)
        frames.append((ftype, payload))
    blob = b"".join(encode_frame(f, p) for f, p in frames)
    out = _drain(reader, blob)
    assert [(f, p) for f, _, p in out] == frames
    assert all(flags == 0 for _, flags, _ in out)
    assert reader.pending_bytes == 0


def test_frame_roundtrip_byte_at_a_time():
    """Arbitrary chunking must not matter: feeding one byte at a time
    yields exactly the same frames, each completing only on its final
    byte (no partial yields)."""
    blob = encode_frame(FT_HELLO, b"x" * 37) + encode_frame(FT_BYE)
    reader = FrameReader()
    out = []
    for i, b in enumerate(blob):
        got = _drain(reader, bytes([b]))
        out.extend(got)
        if got:
            assert i in (len(blob) - 17, len(blob) - 1)
    assert [(f, p) for f, _, p in out] == [(FT_HELLO, b"x" * 37),
                                           (FT_BYE, b"")]


def test_interleaved_frames_one_buffer_split_mid_header():
    """Multiple frames in one feed, with the cut landing mid-header of
    the trailing frame: the complete frames come out, the tail stays
    buffered, and the next feed completes it."""
    a = encode_frame(FT_HELLO, b"one")
    b = encode_frame(FT_SIGNALS, b"two-two")
    c = encode_frame(FT_BYE)
    blob = a + b + c
    cut = len(a) + len(b) + 9           # 9 bytes into c's 16-byte header
    reader = FrameReader()
    out = _drain(reader, blob[:cut])
    assert [(f, p) for f, _, p in out] == [(FT_HELLO, b"one"),
                                           (FT_SIGNALS, b"two-two")]
    assert reader.pending_bytes == 9    # untouched partial header
    out = _drain(reader, blob[cut:])
    assert [(f, p) for f, _, p in out] == [(FT_BYE, b"")]


def test_truncated_frame_consumes_nothing_and_is_not_an_error():
    reader = FrameReader()
    blob = encode_frame(FT_HELLO, b"payload")
    assert _drain(reader, blob[:-1]) == []
    assert reader.pending_bytes == len(blob) - 1
    out = _drain(reader, blob[-1:])     # truncation is just backpressure
    assert [(f, p) for f, _, p in out] == [(FT_HELLO, b"payload")]


# --------------------------------------------------------- malformed input
def _header(magic=wire.MAGIC, version=WIRE_VERSION, ftype=FT_HELLO,
            flags=0, length=0, crc=zlib.crc32(b"")):
    return HEADER.pack(magic, version, ftype, flags, length, crc)


@pytest.mark.parametrize("blob,match", [
    (_header(magic=b"EDIT"), "bad magic"),
    (_header(version=WIRE_VERSION + 1), "unsupported wire version"),
    (_header(ftype=99), "unknown frame type"),
    (_header(flags=0x8000), "reserved flags"),
    (_header(length=MAX_PAYLOAD + 1), "exceeds MAX_PAYLOAD"),
])
def test_bad_headers_rejected_and_poison(blob, match):
    reader = FrameReader()
    with pytest.raises(WireError, match=match):
        _drain(reader, blob)
    # poisoned: nothing after the corruption is trusted
    with pytest.raises(WireError, match="poisoned"):
        _drain(reader, encode_frame(FT_BYE))


def test_crc_mismatch_rejected():
    blob = bytearray(encode_frame(FT_HELLO, b"hello wire"))
    blob[-3] ^= 0xFF                    # flip a payload byte
    reader = FrameReader()
    with pytest.raises(WireError, match="CRC"):
        _drain(reader, bytes(blob))
    with pytest.raises(WireError, match="poisoned"):
        _drain(reader, b"")


def test_valid_frames_before_corruption_still_yielded():
    """A corrupt frame must not smear backwards: frames fully decoded
    from the same feed() call before the bad header still come out
    (generator yields them before raising)."""
    good = encode_frame(FT_HELLO, b"ok")
    reader = FrameReader()
    out = []
    with pytest.raises(WireError, match="bad magic"):
        for frame in reader.feed(good + _header(magic=b"XXXX")):
            out.append(frame)
    assert [(f, p) for f, _, p in out] == [(FT_HELLO, b"ok")]


def test_encode_frame_rejects_bad_type_and_oversize():
    with pytest.raises(WireError, match="unknown frame type"):
        encode_frame(42)
    # fake an oversize payload without allocating 256 MiB
    class _Huge(bytes):
        def __len__(self):
            return MAX_PAYLOAD + 1
    with pytest.raises(WireError, match="exceeds"):
        encode_frame(FT_HELLO, _Huge())


@settings(max_examples=30)
@given(st.integers(min_value=0, max_value=2048),
       st.integers(min_value=1, max_value=64),
       st.integers(min_value=0, max_value=10_000))
def test_fuzz_roundtrip_any_chunking(size, chunk, seed):
    """Property: any payload, cut into any chunk size, round-trips."""
    rng = np.random.RandomState(seed)
    payload = rng.bytes(size)
    blob = encode_frame(FT_SIGNALS, payload)
    reader = FrameReader()
    out = []
    for i in range(0, len(blob), chunk):
        out.extend(_drain(reader, blob[i:i + chunk]))
    assert [(f, p) for f, _, p in out] == [(FT_SIGNALS, payload)]
    assert reader.pending_bytes == 0


@settings(max_examples=30)
@given(st.integers(min_value=1, max_value=512),
       st.integers(min_value=0, max_value=10_000))
def test_fuzz_payload_corruption_never_yields(size, seed):
    """Property: flipping any payload byte kills the frame — WireError,
    zero frames yielded, reader poisoned.  (Header fields have their own
    dedicated rejection tests above.)"""
    rng = np.random.RandomState(seed)
    payload = rng.bytes(size)
    blob = bytearray(encode_frame(FT_DRAFT, payload))
    blob[HEADER.size + rng.randint(size)] ^= 1 + rng.randint(255)
    reader = FrameReader()
    out = []
    with pytest.raises(WireError):
        for frame in reader.feed(bytes(blob)):
            out.append(frame)
    assert out == []


# ---------------------------------------------------------------- payloads
def test_json_payload_roundtrip_and_rejection():
    obj = {"a": 1, "b": [1, 2], "c": {"d": None}}
    assert decode_json(json_payload(obj)) == obj
    with pytest.raises(WireError, match="bad json"):
        decode_json(b"\xff\xfe not json")
    with pytest.raises(WireError, match="must be an object"):
        decode_json(b"[1, 2]")


def test_npz_payload_rejects_garbage():
    with pytest.raises(WireError, match="bad npz"):
        decode_npz(b"PK\x03\x04 definitely not an npz archive")


def test_signals_payload_matches_shard_schema():
    """A SIGNALS frame body IS a spill shard plus ``__baseline__`` —
    dtypes and ragged shapes survive, and the baseline rides along."""
    batches = [
        SignalBatch(np.arange(24, dtype=np.float32).reshape(4, 6),
                    np.arange(4, dtype=np.int32)),
        SignalBatch(np.ones((9, 6), np.float16),
                    np.arange(9, dtype=np.int64)),
    ]
    back, baseline = decode_signals(signals_payload(batches, baseline=0.625))
    assert baseline == 0.625
    assert len(back) == 2
    for orig, got in zip(batches, back):
        np.testing.assert_array_equal(orig.feats, got.feats)
        np.testing.assert_array_equal(orig.tokens, got.tokens)
        assert orig.feats.dtype == got.feats.dtype
        assert orig.tokens.dtype == got.tokens.dtype
    # a non-shard npz is a wire error, not a ValueError leak
    with pytest.raises(WireError, match="not a signal shard"):
        decode_signals(wire.npz_payload({"junk": np.zeros(3)}))


def test_draft_payload_roundtrip_nested_tree():
    dparams = {"fc": {"w": np.arange(6, dtype=np.float32).reshape(2, 3),
                      "b": np.zeros(3, np.float32)},
               "norm": {"scale": np.ones(3, np.float16)}}
    seq, tree, acc = decode_draft(draft_payload(11, dparams, 0.75))
    assert seq == 11 and acc == 0.75
    assert set(tree) == {"fc", "norm"}
    np.testing.assert_array_equal(tree["fc"]["w"], dparams["fc"]["w"])
    np.testing.assert_array_equal(tree["fc"]["b"], dparams["fc"]["b"])
    assert tree["norm"]["scale"].dtype == np.float16


def test_draft_payload_missing_fields_rejected():
    with pytest.raises(WireError, match="missing"):
        decode_draft(wire.npz_payload(
            {"p/w": np.zeros(2), "__eval_acc__": np.asarray(0.5)}))
    with pytest.raises(WireError, match="no parameters"):
        decode_draft(wire.npz_payload(
            {"__seq__": np.asarray(1), "__eval_acc__": np.asarray(0.5)}))


def test_config_dict_roundtrip():
    from conftest import tiny_cfg
    from repro.models.config import MLA, BlockDef, FFN_SWIGLU
    cfg = tiny_cfg(name="wire", pattern=(BlockDef(MLA, FFN_SWIGLU),),
                   capture_layers=(0, 1, 1))
    back = wire.config_from_dict(wire.config_to_dict(cfg))
    assert back == cfg


def test_parse_endpoint():
    assert wire.parse_endpoint("unix:/tmp/x.sock") == ("unix", "/tmp/x.sock")
    assert wire.parse_endpoint("tcp:127.0.0.1:9000") == \
        ("tcp", ("127.0.0.1", 9000))
    for bad in ("unix:", "tcp:nohostport", "http://x", "spawn"):
        with pytest.raises(ValueError):
            wire.parse_endpoint(bad)
