"""Codec tests: native & NumPy backends, cross-decodability, error bounds.

Capability parity target: the reference's ZFP+LZ4 payload stack
(reference src/dispatcher.py:81-84) — here symmetric and first-party.
"""

import numpy as np
import pytest

from defer_tpu.codec import (BlockFloatCodec, LosslessCodec, PipelineCodec,
                             RawCodec, native_available)

RNG = np.random.RandomState(42)


def test_native_library_builds():
    assert native_available(), "g++ toolchain present; native codec must load"


@pytest.mark.parametrize("force_numpy", [False, True])
def test_blockfloat_roundtrip_error_bound(force_numpy):
    c = BlockFloatCodec(bits=8, force_numpy=force_numpy)
    x = RNG.randn(3, 57, 11).astype(np.float32) * 10
    data = c.encode(x)
    y = c.decode(data, x.shape)
    # error bounded by block max * 2^-(bits-1)
    bound = np.abs(x).max() * 2.0 ** -(c.bits - 1) + 1e-7
    assert np.abs(x - y).max() <= bound
    # fixed rate: ~bits/32 of float32 size + exponent overhead
    assert len(data) < x.nbytes * (c.bits / 32.0) * 1.2 + 64


def test_blockfloat_cross_backend_compatible():
    """Native and NumPy implement the identical BFC1 wire format."""
    cn = BlockFloatCodec(bits=7)
    cp = BlockFloatCodec(bits=7, force_numpy=True)
    x = RNG.randn(1000).astype(np.float32)
    assert cn.encode(x) == cp.encode(x)
    np.testing.assert_array_equal(cn.decode(cp.encode(x), x.shape),
                                  cp.decode(cn.encode(x), x.shape))


@pytest.mark.parametrize("force_numpy", [False, True])
def test_blockfloat_edge_cases(force_numpy):
    c = BlockFloatCodec(bits=8, force_numpy=force_numpy)
    for x in [np.zeros((64,), np.float32),
              np.zeros((0,), np.float32),
              np.array([1e-30, -1e30, 0, np.inf, -np.inf, np.nan],
                       np.float32),
              np.full((65,), 7.25, np.float32)]:
        y = c.decode(c.encode(x), x.shape)
        assert y.shape == x.shape
        finite = np.isfinite(x)
        # non-finite values are flushed to 0 by design
        assert np.isfinite(y).all()
        if finite.all() and x.size:
            assert np.abs(x - y).max() <= np.abs(x).max() * 2**-7 + 1e-7


@pytest.mark.parametrize("force_numpy", [False, True])
def test_lossless_roundtrip(force_numpy):
    c = LosslessCodec(force_numpy=force_numpy)
    for x in [RNG.randint(0, 255, 10_000).astype(np.uint8),
              np.tile(np.arange(100, dtype=np.int32), 50),
              RNG.randn(999).astype(np.float32),
              np.zeros((4096,), np.float32)]:
        y = c.decode(c.encode(x), x.shape, x.dtype)
        np.testing.assert_array_equal(x, y)


def test_lzb_compresses_redundancy():
    c = LosslessCodec()
    x = np.zeros((100_000,), np.uint8)
    assert len(c.encode(x)) < 3000  # ~3 bytes per max-length match token
    text = np.frombuffer(b"the quick brown fox " * 500, np.uint8)
    assert len(c.encode(text)) < text.size // 5


def test_lzb_cross_backend_compatible():
    cn = LosslessCodec()
    cp = LosslessCodec(force_numpy=True)
    x = np.tile(RNG.randint(0, 9, 100).astype(np.uint8), 30)
    # formats interchange even if greedy matches differ
    np.testing.assert_array_equal(
        cn.decode(cp.encode(x), x.shape, x.dtype), x)
    np.testing.assert_array_equal(
        cp.decode(cn.encode(x), x.shape, x.dtype), x)


@pytest.mark.parametrize("force_numpy", [False, True])
def test_pipeline_codec_stack(force_numpy):
    """The full lz(blockfloat(x)) stack the reference pioneered, symmetric."""
    c = PipelineCodec(bits=8, force_numpy=force_numpy)
    x = RNG.randn(32, 56, 56).astype(np.float32)
    y = c.decode(c.encode(x), x.shape)
    assert np.abs(x - y).max() <= np.abs(x).max() * 2**-7 + 1e-7


def test_corrupt_payloads_rejected():
    c = PipelineCodec()
    with pytest.raises(ValueError):
        c.decode(b"garbage!", (2,))
    bf = BlockFloatCodec()
    with pytest.raises(ValueError):
        bf.decode(b"NOPE" + b"\x00" * 20, (2,))
    lz = LosslessCodec()
    with pytest.raises(ValueError):
        lz.decode(b"LZB1\xff\xff\xff\xff\xff\xff\xff\xff\xff\xff", (2,),
                  np.uint8)


def test_raw_codec():
    c = RawCodec()
    x = RNG.randn(5, 5).astype(np.float32)
    np.testing.assert_array_equal(c.decode(c.encode(x), x.shape, x.dtype), x)


@pytest.mark.parametrize("force_numpy", [False, True])
def test_blockfloat_extreme_exponents(force_numpy):
    """Exponent-byte saturation: huge values clamp toward 2^127, subnormal
    blocks flush toward 0 — never wrap (regression for the e+128 overflow)."""
    c = BlockFloatCodec(bits=8, force_numpy=force_numpy)
    huge = np.full((64,), 3e38, np.float32)
    got = c.decode(c.encode(huge), huge.shape)
    assert got.max() > 1e38  # same order of magnitude, not 1e-39
    tiny = np.full((64,), 1e-40, np.float32)
    got = c.decode(c.encode(tiny), tiny.shape)
    assert np.abs(got).max() < 1e-30  # flushed toward zero, not 1e+37


def test_blockfloat_extreme_cross_backend_identical():
    cn = BlockFloatCodec(bits=8)
    cp = BlockFloatCodec(bits=8, force_numpy=True)
    for x in (np.full((64,), 3e38, np.float32),
              np.full((64,), 1e-40, np.float32),
              np.array([2.0**-130, 2.0**127], np.float32)):
        assert cn.encode(x) == cp.encode(x)


@pytest.mark.parametrize("force_numpy", [False, True])
def test_hostile_size_headers_rejected_before_allocating(force_numpy):
    """A tiny payload whose header declares a multi-terabyte output must be
    rejected by validating against the caller's expected shape — not by
    attempting the allocation."""
    bomb_bf = (b"BFC1" + (2 ** 40).to_bytes(8, "little")
               + bytes([8, 0, 0, 0]))
    with pytest.raises(ValueError):
        BlockFloatCodec(bits=8, force_numpy=force_numpy).decode(
            bomb_bf, (64,))
    c = LosslessCodec(force_numpy=force_numpy)
    payload = c.encode(np.zeros(64, np.uint8))
    with pytest.raises(ValueError):
        c.decode(payload, (2 ** 40,), np.uint8)  # size mismatch, no alloc


def test_lzb_expansion_worst_case_bound():
    """Regression for a heap overflow: alternating [len-4 match at long
    distance][1-byte literal] expands to ~1.2x the input — more than the
    old all-literals bound (n + n/128) — and corrupted the heap on real
    multi-MB activation payloads.  The adversarial payload below forces
    that pattern; the encoder must stay within lzb_max_compressed_size,
    round-trip exactly, and agree bit-for-bit across backends."""
    rng = np.random.default_rng(0)
    a = rng.integers(0, 256, 60000, dtype=np.uint8).tobytes()
    b = bytearray(a)
    for j in range(0, len(b), 5):
        b[j] = (b[j] + 1) % 256  # break every 5th byte of the repeat
    payload = np.frombuffer(a + bytes(b), np.uint8)

    native_codec = LosslessCodec()
    py_codec = LosslessCodec(force_numpy=True)
    enc_n = native_codec.encode(payload)
    enc_p = py_codec.encode(payload)
    assert enc_n == enc_p  # backends share the exact format
    # the expansion is real (this is what broke the old bound) ...
    n = payload.size
    assert len(enc_n) > n + n // 128 + 24
    # ... and both directions stay correct
    for codec, enc in ((native_codec, enc_n), (py_codec, enc_p)):
        dec = codec.decode(enc, payload.shape, payload.dtype)
        np.testing.assert_array_equal(dec, payload)


def test_ensure_built_contract(tmp_path):
    """Shared native builder: builds when missing, rebuilds when the
    source is as new or newer, refuses to bless a stale .so when the
    rebuild fails (callers then use their NumPy fallback, never stale
    code — and say so)."""
    import os
    import shutil
    import time

    from defer_tpu.utils._nativebuild import ensure_built

    if shutil.which("g++") is None:
        pytest.skip("no toolchain")
    src = tmp_path / "m.cpp"
    so = tmp_path / "m.so"
    src.write_text('extern "C" int f() { return 1; }\n')
    assert ensure_built(str(src), str(so))
    assert so.exists()
    first = so.stat().st_mtime_ns

    # fresh so, strictly older src: no rebuild
    assert ensure_built(str(src), str(so))
    assert so.stat().st_mtime_ns == first

    # a source edit in the SAME clock tick as the build is stale (>=):
    # the binary may predate the edit
    os.utime(src, ns=(first, first))
    assert ensure_built(str(src), str(so))
    assert so.stat().st_mtime_ns > first
    first = so.stat().st_mtime_ns

    # newer src: rebuild happens (mtime moves)
    time.sleep(0.01)
    src.write_text('extern "C" int f() { return 2; }\n')
    os.utime(src, ns=(time.time_ns(), time.time_ns()))
    assert ensure_built(str(src), str(so))
    assert so.stat().st_mtime_ns > first

    # newer src that fails to compile: False, and no half-written temp
    time.sleep(0.01)
    src.write_text("this is not C++\n")
    os.utime(src, ns=(time.time_ns(), time.time_ns()))
    assert not ensure_built(str(src), str(so))
    assert not [p for p in tmp_path.iterdir() if ".build." in p.name]


def test_native_loader_says_which_path_it_took(tmp_path, monkeypatch,
                                                capfd):
    """A failed build is not a silent switch to NumPy: the loader names
    the library and the path it takes instead."""
    from defer_tpu.utils import _nativebuild
    (tmp_path / "codec.cpp").write_text("this is not C++\n")
    monkeypatch.setattr(_nativebuild, "NATIVE_DIR", str(tmp_path))
    assert _nativebuild.load_library("codec", "libx.so", "NumPy codec") \
        is None
    err = capfd.readouterr().err
    assert "libx.so failed" in err
    assert "native codec library unavailable; taking the NumPy codec " \
        "path" in err
