"""Checkpoint store integrity: atomic single-point commit of arrays +
metadata, content checksums verified on load, last-known-good fallback
for corrupt/truncated files, and the validate-before-trust resume
contract of load_training_state."""
import hashlib
import json
import os
import random
import time
import warnings

import jax
import numpy as np
import pytest

from repro import tracing
from repro.checkpoint import store
from repro.checkpoint.store import (META_KEY, CheckpointCorruptError,
                                    load_checkpoint, load_metadata,
                                    load_training_state, save_checkpoint,
                                    verify_checkpoint)
from repro.optim.adamw import init_opt_state


def tree(seed, scale=1.0):
    rng = np.random.RandomState(seed)
    return {"w": rng.randn(4, 4).astype(np.float32) * scale,
            "b": rng.randn(4).astype(np.float32) * scale}


def assert_tree_equal(a, b):
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))


# ----------------------------------------------------- atomic commit

def test_metadata_is_bundled_inside_the_npz(tmp_path):
    """Arrays and metadata commit at ONE atomic point: the npz itself
    carries the metadata, so no crash window can pair new arrays with
    stale metadata."""
    p = str(tmp_path / "c.npz")
    save_checkpoint(p, tree(0), {"step": 7, "loss": 1.5})
    with np.load(p) as data:
        assert META_KEY in data
        meta = json.loads(bytes(data[META_KEY].tobytes()).decode())
    assert meta["step"] == 7
    assert "checksum" in meta
    assert meta["checksum_algo"] == store.CHECKSUM_ALGO


def test_no_stray_temp_files_after_save(tmp_path):
    p = str(tmp_path / "c.npz")
    save_checkpoint(p, tree(0), {"step": 1})
    names = set(os.listdir(tmp_path))
    assert not any(n.endswith(".tmp") for n in names)


def test_sidecar_still_written_and_metadata_prefers_bundle(tmp_path):
    p = str(tmp_path / "c.npz")
    save_checkpoint(p, tree(0), {"step": 3})
    assert os.path.exists(p + ".meta.json")
    # poison the sidecar: the bundled copy must win
    with open(p + ".meta.json", "w") as f:
        json.dump({"step": 999}, f)
    assert load_metadata(p)["step"] == 3
    assert "checksum" not in load_metadata(p)
    assert "checksum_algo" not in load_metadata(p)


def test_legacy_sidecar_fallback(tmp_path):
    """A checkpoint with no bundled metadata (pre-checksum format or
    missing file) falls back to the .meta.json sidecar."""
    p = str(tmp_path / "c.npz")
    with open(p + ".meta.json", "w") as f:
        json.dump({"step": 11}, f)
    assert load_metadata(p)["step"] == 11


# --------------------------------------------------------- checksums

def test_roundtrip_verifies_checksum(tmp_path):
    p = str(tmp_path / "c.npz")
    t = tree(1)
    save_checkpoint(p, t, {"step": 5})
    assert verify_checkpoint(p)["step"] == 5
    out = load_checkpoint(p, tree(99))
    assert_tree_equal(out, t)


def test_truncated_file_raises_corrupt(tmp_path):
    p = str(tmp_path / "c.npz")
    save_checkpoint(p, tree(1), {"step": 5}, keep_previous=False)
    size = os.path.getsize(p)
    with open(p, "r+b") as f:
        f.truncate(size // 2)
    with pytest.raises(CheckpointCorruptError):
        verify_checkpoint(p)
    with pytest.raises(CheckpointCorruptError):
        load_checkpoint(p, tree(1))


def test_bitflip_fails_checksum(tmp_path):
    """Same length, flipped payload bytes: only a CONTENT checksum
    catches this (zip structure can stay parseable)."""
    p = str(tmp_path / "c.npz")
    t = tree(1)
    save_checkpoint(p, t, {"step": 5}, keep_previous=False)
    with open(p, "rb") as f:
        blob = bytearray(f.read())
    # npz members are stored uncompressed: locate w's raw payload and
    # flip bytes there (zip structure and npy headers stay intact)
    off = blob.find(t["w"].tobytes())
    assert off > 0
    for i in range(off, off + 8):
        blob[i] ^= 0xFF
    with open(p, "wb") as f:
        f.write(blob)
    with pytest.raises(CheckpointCorruptError):
        load_checkpoint(p, tree(1))


def test_missing_array_raises_corrupt(tmp_path):
    p = str(tmp_path / "c.npz")
    save_checkpoint(p, {"w": np.zeros(3, np.float32)}, {"step": 1})
    with pytest.raises(CheckpointCorruptError, match="missing array"):
        load_checkpoint(p, tree(0))


# ------------------------------------------------- last-known-good

def training_tree(seed):
    # the {"params", "opt"} layout load_training_state restores into
    return {"params": {"w": tree(seed)["w"]}, "opt": {"b": tree(seed)["b"]}}


def test_prev_rotation(tmp_path):
    p = str(tmp_path / "c.npz")
    save_checkpoint(p, tree(1), {"step": 10})
    save_checkpoint(p, tree(2), {"step": 20})
    assert verify_checkpoint(p)["step"] == 20
    assert verify_checkpoint(p + ".prev")["step"] == 10
    assert_tree_equal(load_checkpoint(p + ".prev", tree(0)), tree(1))


def test_load_training_state_falls_back_to_prev(tmp_path):
    p = str(tmp_path / "c.npz")
    save_checkpoint(p, training_tree(1), {"step": 10})
    save_checkpoint(p, training_tree(2), {"step": 20})
    with open(p, "r+b") as f:
        f.truncate(os.path.getsize(p) // 2)
    with pytest.warns(RuntimeWarning, match="previous good checkpoint"):
        params, _, step = load_training_state(
            p, {"w": tree(0)["w"]}, {"b": tree(0)["b"]})
    assert step == 10
    np.testing.assert_array_equal(np.asarray(params["w"]), tree(1)["w"])


def test_load_training_state_step0_when_all_corrupt(tmp_path):
    p = str(tmp_path / "c.npz")
    save_checkpoint(p, training_tree(1), {"step": 10})
    save_checkpoint(p, training_tree(2), {"step": 20})
    for q in (p, p + ".prev"):
        with open(q, "r+b") as f:
            f.truncate(os.path.getsize(q) // 2)
    fresh_p, fresh_o = {"w": tree(7)["w"]}, {"b": tree(7)["b"]}
    with pytest.warns(RuntimeWarning):
        params, opt, step = load_training_state(p, fresh_p, fresh_o)
    assert step == 0
    assert params is fresh_p and opt is fresh_o


def test_load_training_state_clean_paths(tmp_path):
    p = str(tmp_path / "c.npz")
    # no checkpoint at all: inputs unchanged, step 0, NO warning
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        params, opt, step = load_training_state(
            p, {"w": tree(0)["w"]}, {"b": tree(0)["b"]})
    assert step == 0
    save_checkpoint(p, training_tree(3), {"step": 42})
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        params, _, step = load_training_state(
            p, {"w": tree(0)["w"]}, {"b": tree(0)["b"]})
    assert step == 42
    np.testing.assert_array_equal(np.asarray(params["w"]), tree(3)["w"])


# ------------------------------------------------ chunked checksum

def reference_chunked(arrays, piece):
    """The chunked scheme written out serially, as the format is
    specified: per array in sorted key order, a JSON framing line (name,
    dtype, shape, piece count), then the sha256 of each piece of its
    bytes."""
    h = hashlib.sha256()
    for key in sorted(arrays):
        a = arrays[key]
        raw = a.tobytes()
        cuts = [raw[i:i + piece] for i in range(0, len(raw), piece)]
        h.update((json.dumps([key, str(a.dtype), list(a.shape), len(cuts)])
                  + "\n").encode())
        for c in cuts:
            h.update(hashlib.sha256(c).digest())
    return h.hexdigest()


def reference_legacy(arrays):
    """The serial sha256 of checkpoints written before the scheme
    field: name, dtype, shape and a copy of the bytes, per array."""
    h = hashlib.sha256()
    for key in sorted(arrays):
        a = np.ascontiguousarray(arrays[key])
        h.update(key.encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.fixture
def small_pieces(monkeypatch):
    """16-byte pieces: tree()'s 4x4 float32 "w" spans four, "b" one."""
    monkeypatch.setattr(store, "PIECE_BYTES", 16)
    return 16


def read_npz(p):
    with np.load(p) as data:
        arrays = dict(data)
    meta = json.loads(arrays.pop(META_KEY).tobytes().decode())
    return arrays, meta


def write_npz(p, arrays, meta):
    payload = dict(arrays)
    payload[META_KEY] = np.frombuffer(json.dumps(meta).encode(), np.uint8)
    with open(p, "wb") as f:
        np.savez(f, **payload)


def flip_byte(p, key, at):
    """Rewrite the checkpoint at ``p`` with byte ``at`` of array ``key``
    flipped and its metadata as it was: the zip's own CRCs match the new
    bytes, so only the content checksum can tell."""
    arrays, meta = read_npz(p)
    raw = arrays[key].reshape(-1).view(np.uint8)
    raw[at] ^= 0x01
    write_npz(p, arrays, meta)


@pytest.mark.parametrize("flip", [None, "first", "last"])
def test_multipiece_checkpoint(tmp_path, small_pieces, flip):
    """A leaf hashed as several pieces round-trips and verifies; one
    byte flipped in its first or its last piece fails.  The save's
    ``checkpoint.hash`` is one span directly under ``checkpoint``, the
    pool's threads open none, and the pieces are counted beside the
    bytes."""
    p = str(tmp_path / "c.npz")
    t = tree(3)
    with tracing.span("checkpoint") as sp:
        save_checkpoint(p, t, {"step": 9}, keep_previous=False)
    hashes = [s for s in tracing.spans()
              if s.parent == sp.id and s.name == "checkpoint.hash"]
    assert len(hashes) == 1
    assert not [s for s in tracing.spans() if s.parent == hashes[0].id]
    assert sp.counters["checkpoint.bytes"] == 80
    assert sp.counters["checkpoint.hash_pieces"] == 5
    arrays, meta = read_npz(p)
    assert meta["checksum_algo"] == store.CHECKSUM_ALGO
    assert meta["checksum"] == reference_chunked(arrays, small_pieces)
    if flip is None:
        assert verify_checkpoint(p)["step"] == 9
        assert_tree_equal(load_checkpoint(p, tree(0)), t)
        return
    flip_byte(p, "w", 0 if flip == "first" else 63)
    with pytest.raises(CheckpointCorruptError, match="content checksum"):
        verify_checkpoint(p)


@pytest.mark.parametrize("change", ["dtype", "shape", "key"])
def test_framing_change_with_identical_bytes_fails(tmp_path, small_pieces,
                                                   change):
    """The same bytes under another dtype, shape or name fail the
    checksum: each leaf's framing is hashed, not only its pieces."""
    p = str(tmp_path / "c.npz")
    save_checkpoint(p, tree(5), {"step": 2}, keep_previous=False)
    arrays, meta = read_npz(p)
    w = arrays.pop("w")
    if change == "dtype":
        arrays["w"] = w.view(np.int32)
    elif change == "shape":
        arrays["w"] = w.reshape(2, 8)
    else:
        arrays["v"] = w
    write_npz(p, arrays, meta)
    with pytest.raises(CheckpointCorruptError, match="content checksum"):
        verify_checkpoint(p)


@pytest.mark.parametrize("workers,shuffle", [(1, False), (3, False),
                                             (64, False), (64, True)])
def test_checksum_independent_of_pool(small_pieces, monkeypatch, workers,
                                      shuffle):
    """The checksum is the format's, whatever the pool's size and the
    order in which its pieces finish."""
    arrays = {"w": np.arange(40, dtype=np.float32).reshape(5, 8),
              "b": np.arange(3, dtype=np.int64),
              "s": np.zeros((), np.float32), "e": np.zeros((0, 4), np.int8)}
    monkeypatch.setattr(store, "_usable_cpus", lambda: workers)
    if shuffle:
        rng = random.Random(0)
        sha = store._sha256

        def late(buf):
            time.sleep(rng.random() / 100)
            return sha(buf)
        monkeypatch.setattr(store, "_sha256", late)
    got, pieces = store._content_checksum(arrays)
    assert pieces == 10 + 2 + 1 + 0
    assert got == reference_chunked(arrays, small_pieces)


@pytest.mark.parametrize("flip", [None, "params/w", "opt/step"])
def test_legacy_checkpoint(tmp_path, flip):
    """A training checkpoint written before the scheme field (one serial
    sha256, no "checksum_algo") still verifies and resumes, 0-d AdamW
    step included; one flipped byte, in a matrix or in the scalar, still
    fails it."""
    p = str(tmp_path / "c.npz")
    params = {"w": tree(6)["w"]}
    opt = jax.tree.map(np.asarray, init_opt_state(params))
    opt["step"] = np.asarray(12, np.int32)
    arrays = store._flatten_with_paths({"params": params, "opt": opt})
    assert arrays["opt/step"].ndim == 0
    write_npz(p, arrays, {"step": 12, "checksum": reference_legacy(arrays)})
    if flip is not None:
        flip_byte(p, flip, 0)
        with pytest.raises(CheckpointCorruptError, match="content checksum"):
            verify_checkpoint(p)
        return
    assert verify_checkpoint(p)["step"] == 12
    like = {"w": tree(0)["w"]}
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        got, got_opt, step = load_training_state(p, like,
                                                 init_opt_state(like))
    assert step == 12
    np.testing.assert_array_equal(np.asarray(got["w"]), params["w"])
    assert int(got_opt["step"]) == 12


@pytest.mark.parametrize("algo", ["sha256-chunked-1MiB", "md5", ""])
def test_unknown_checksum_scheme_is_refused(tmp_path, algo):
    p = str(tmp_path / "c.npz")
    save_checkpoint(p, tree(7), {"step": 3}, keep_previous=False)
    arrays, meta = read_npz(p)
    meta["checksum_algo"] = algo
    write_npz(p, arrays, meta)
    with pytest.raises(CheckpointCorruptError, match="unknown checksum"):
        verify_checkpoint(p)
