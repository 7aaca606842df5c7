"""Native host-runtime library: build, parity with numpy paths, codec
fuzzing, hash index semantics, fallbacks (the FRocksDB/lz4-JNI analog
layer — see flink_tpu/native/native.cpp)."""

import os
import pickle

import numpy as np
import pytest

from flink_tpu import native
from flink_tpu.core.keygroups import (
    key_groups_for_hash_batch, murmur_mix,
)

RNG = np.random.default_rng(42)


def test_native_builds():
    # the toolchain is baked into the image; the native path must be live
    assert native.NATIVE_AVAILABLE


def test_murmur_parity_with_numpy():
    codes = RNG.integers(0, 1 << 32, 100_000, dtype=np.uint32)
    assert np.array_equal(native.murmur_mix_batch(codes), murmur_mix(codes))
    # edge codes: 0, max, the INT32_MIN-producing neighborhood
    edge = np.array([0, 0xFFFFFFFF, 1, 0x80000000], dtype=np.uint32)
    assert np.array_equal(native.murmur_mix_batch(edge), murmur_mix(edge))


def test_key_group_batch_parity():
    codes = RNG.integers(0, 1 << 32, 50_000, dtype=np.uint32)
    for maxp in (128, 1 << 15, 7):
        a = native.key_group_batch(codes, maxp)
        b = (murmur_mix(codes) % np.int32(maxp)).astype(np.int32)
        assert np.array_equal(a, b)
    # the integrated hot path (>=512 keys routes native)
    kg = key_groups_for_hash_batch(codes, 128)
    assert np.array_equal(kg, (murmur_mix(codes) % np.int32(128)
                               ).astype(np.int32))


@pytest.mark.parametrize("payload", [
    b"",
    b"a",
    b"hello world " * 5000,                       # highly compressible
    bytes(RNG.integers(0, 256, 100_000, dtype=np.uint8)),   # random
    b"ab" * 100_000,                              # tiny period
    bytes(RNG.integers(0, 4, 50_000, dtype=np.uint8)),      # low entropy
    pickle.dumps({"state": np.arange(10_000), "x": list(range(1000))}),
])
def test_codec_roundtrip(payload):
    c = native.compress(payload)
    assert native.decompress(c) == payload


def test_codec_compresses():
    data = b"0123456789" * 10_000
    assert len(native.compress(data)) < len(data) // 5


def test_codec_fuzz_roundtrip():
    for trial in range(30):
        n = int(RNG.integers(0, 5000))
        # mix of runs and noise
        parts = []
        while sum(map(len, parts)) < n:
            if RNG.random() < 0.5:
                parts.append(bytes([int(RNG.integers(0, 256))])
                             * int(RNG.integers(1, 300)))
            else:
                parts.append(bytes(RNG.integers(0, 256,
                                                int(RNG.integers(1, 100)),
                                                dtype=np.uint8)))
        data = b"".join(parts)[:n]
        assert native.decompress(native.compress(data)) == data


def test_decompress_rejects_corrupt():
    good = native.compress(b"hello world " * 100)
    with pytest.raises((ValueError, RuntimeError)):
        native.decompress(b"\x09" + good[1:])   # unknown tag
    if native.NATIVE_AVAILABLE:
        # truncated native frame
        with pytest.raises(ValueError):
            native.decompress(good[: len(good) // 2])


def test_pure_python_decoder_parity():
    """Native-compressed frames must decode without the library (durable
    checkpoints restored on a toolchain-less host)."""
    from flink_tpu.native import _TAG_NATIVE, _py_block_decompress
    for payload in (b"", b"x", b"hello world " * 3000,
                    bytes(RNG.integers(0, 256, 20_000, dtype=np.uint8)),
                    b"ab" * 40_000):
        frame = native.compress(payload)
        assert frame[:1] == _TAG_NATIVE
        assert _py_block_decompress(frame[1:]) == payload


def test_hash_index_upsert_lookup():
    hi = native.HostHashIndex(4)
    keys = np.array([10, 20, 10, 30, 20, 40], dtype=np.int64)
    slots = hi.upsert(keys)
    assert list(slots) == [0, 1, 0, 2, 1, 3]
    assert len(hi) == 4
    found = hi.lookup(np.array([30, 99, 10], dtype=np.int64))
    assert list(found) == [2, -1, 0]


def test_hash_index_int64_min_not_conflated():
    """INT64_MIN is the table sentinel; it must still be a distinct key
    (regression: it used to be remapped onto INT64_MIN+1)."""
    hi = native.HostHashIndex(4)
    lo = np.iinfo(np.int64).min
    ks = np.array([lo, lo + 1, lo], dtype=np.int64)
    assert list(hi.upsert(ks)) == [0, 1, 0]
    assert list(hi.lookup(np.array([lo + 1, lo], dtype=np.int64))) == [1, 0]


def test_hash_index_growth_and_negative_keys():
    hi = native.HostHashIndex(4)
    keys = RNG.integers(-(1 << 62), 1 << 62, 10_000, dtype=np.int64)
    uniq = np.unique(keys)
    slots = hi.upsert(keys)
    assert len(hi) == len(uniq)
    # same key always maps to the same slot
    slots2 = hi.upsert(keys)
    assert np.array_equal(slots, slots2)
    # parity with the dict fallback
    ref: dict = {}
    expect = np.array([ref.setdefault(int(k), len(ref)) for k in keys],
                      dtype=np.int32)
    assert np.array_equal(slots, expect)


def test_compressed_checkpoint_storage_roundtrip(tmp_path):
    from flink_tpu.checkpoint.storage import (
        CompletedCheckpoint, FsCheckpointStorage,
    )
    st = FsCheckpointStorage(str(tmp_path))
    cp = CompletedCheckpoint(
        checkpoint_id=7, timestamp=123.0,
        task_snapshots={"v0#0": {"chain": {"op": {
            "keyed": {"backend": {"t": {0: {1: np.arange(100)}}}}}}}},
        vertex_parallelism={"v0": 1})
    stored = st.store(cp)
    loaded = st.load(stored.external_path)
    assert loaded.checkpoint_id == 7
    arr = loaded.task_snapshots["v0#0"]["chain"]["op"]["keyed"][
        "backend"]["t"][0][1]
    assert np.array_equal(arr, np.arange(100))


def test_only_the_hash_named_artifact_is_loaded(tmp_path, monkeypatch):
    """The loaded library is _native-<hash of native.cpp + build command>.so;
    a stray _native.so (say, built for another machine's CPU and carried
    over by a copy of the checkout) is never opened, and a changed source
    or flag names a different artifact, so it is rebuilt."""
    import shutil

    here = tmp_path / "native"
    here.mkdir()
    shutil.copy(native._SRC, here / "native.cpp")
    (here / "_native.so").write_bytes(b"not an ELF file")
    monkeypatch.setattr(native, "_HERE", str(here))
    monkeypatch.setattr(native, "_SRC", str(here / "native.cpp"))
    monkeypatch.setattr(native, "_lib", None)
    want = native._artifact_path()
    assert os.path.basename(want).startswith("_native-")
    assert "-march=native" not in native._BUILD_CMD
    lib = native._load()
    assert lib is not None and lib._name == want
    assert sorted(p.name for p in here.glob("*.so")) == sorted(
        ["_native.so", os.path.basename(want)])
    monkeypatch.setattr(native, "_BUILD_CMD", (*native._BUILD_CMD, "-O2"))
    assert native._artifact_path() != want
    with open(here / "native.cpp", "a") as f:
        f.write("\n// edited\n")
    monkeypatch.setattr(native, "_BUILD_CMD", native._BUILD_CMD[:-1])
    assert native._artifact_path() != want
