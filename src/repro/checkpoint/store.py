"""npz-based pytree checkpoint store with end-to-end integrity.

Used by Saturn's introspection mechanism (checkpoint + relaunch when the
solver produces a new plan), by the execution backends' preemption and
crash-recovery paths, and by the end-to-end training examples.

Commit protocol (single atomic commit point):

- The array payload AND the JSON metadata (step counter, loss, content
  checksum) are bundled into ONE ``.npz`` written to a temp file and
  published with a single ``os.replace`` — there is no window in which
  a reader can observe new arrays with stale metadata (the historical
  two-file race: the ``.meta.json`` sidecar used to be written after,
  and non-atomically, so a crash between the two resumed at a stale
  step).
- Before publishing, the previous checkpoint is rotated to
  ``path + ".prev"`` — the last-known-good fallback
  :func:`load_training_state` resumes from when the current file turns
  out corrupt or truncated (e.g. the process was SIGKILLed mid-write of
  something else entirely, or the disk lied).
- A sha256 content checksum over every byte of every array, with each
  array's name, dtype and shape, is stored in the bundled metadata
  under ``"checksum"`` and verified by :func:`load_checkpoint` and
  :func:`verify_checkpoint`; mismatch raises
  :class:`CheckpointCorruptError`.  ``"checksum_algo"`` names its
  scheme: :data:`CHECKSUM_ALGO` hashes fixed 64 MiB pieces of the raw
  bytes on a thread pool and folds their digests in order (see
  :func:`_content_checksum`).  A file without the field predates it and
  is verified with the one serial sha256 it was written with; a scheme
  this module does not know is refused as corrupt.
- A ``.meta.json`` sidecar is still written (atomically, after the
  commit) as a human-inspectable convenience, but the bundled metadata
  is authoritative: :func:`load_metadata` prefers it.
"""
from __future__ import annotations

import hashlib
import json
import os
import tempfile
import warnings
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Optional, Tuple

import jax
import numpy as np

from .. import tracing

# npz entry under which the JSON metadata (incl. checksum) is bundled;
# the name cannot collide with pytree paths (they never start with "__")
META_KEY = "__saturn_meta__"

# The content checksum's scheme, recorded as the bundled metadata's
# "checksum_algo"; PIECE_BYTES is part of the format, not a tuning knob:
# another piece size gives another checksum.
CHECKSUM_ALGO = "sha256-chunked-64MiB"
PIECE_BYTES = 64 << 20


class CheckpointCorruptError(RuntimeError):
    """The checkpoint file is unreadable or fails its content checksum."""


def _flatten_with_paths(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = {}
    for path, leaf in flat:
        key = "/".join(
            str(p.key) if hasattr(p, "key") else str(p.idx) for p in path)
        arr = np.asarray(leaf)
        if arr.dtype.kind not in "fiub" or arr.dtype.itemsize == 0 or \
                str(arr.dtype) == "bfloat16":
            arr = np.asarray(leaf, dtype=np.float32)  # bf16 etc: lossless up
        out[key] = arr
    return out


def _raw_bytes(arr: np.ndarray) -> np.ndarray:
    """The array's bytes in C order as a flat uint8 view (a copy only
    where the array is not C-contiguous)."""
    return np.ascontiguousarray(arr).reshape(-1).view(np.uint8)


def _sha256(buf) -> bytes:
    return hashlib.sha256(buf).digest()


def _usable_cpus() -> int:
    """The cores this process may run on (its affinity mask where the
    platform has one), else the machine's count."""
    if hasattr(os, "sched_getaffinity"):
        return max(1, len(os.sched_getaffinity(0)))
    return os.cpu_count() or 1


def _digests(pieces: list) -> list:
    """Each piece's sha256 digest, in order: a single piece inline, more
    on a pool of up to one thread per core this process may run on."""
    if len(pieces) <= 1:
        return [_sha256(p) for p in pieces]
    with ThreadPoolExecutor(min(len(pieces), _usable_cpus())) as pool:
        return list(pool.map(_sha256, pieces))


def _content_checksum(arrays: dict) -> Tuple[str, int]:
    """(checksum, pieces hashed) of :data:`CHECKSUM_ALGO`.

    Each array, in sorted key order, is cut into :data:`PIECE_BYTES`
    pieces of its raw bytes (zero-copy views); every piece is hashed
    with sha256, on a thread pool when there is more than one (hashlib
    releases the GIL on large buffers).  The checksum is one sha256
    over, per array in order, its framing (name, dtype, shape, piece
    count as one JSON line) followed by its pieces' digests: the same
    whatever the pool's size or the order in which pieces finish, and
    invariant to npz member ordering."""
    frames, pieces = [], []
    for key in sorted(arrays):
        arr = arrays[key]
        raw = _raw_bytes(arr)
        starts = range(0, raw.size, PIECE_BYTES)
        frame = json.dumps([key, str(arr.dtype), list(arr.shape),
                            len(starts)]) + "\n"
        frames.append((frame.encode(), len(starts)))
        pieces.extend(raw[i:i + PIECE_BYTES] for i in starts)
    digests = iter(_digests(pieces))
    h = hashlib.sha256()
    for frame, n in frames:
        h.update(frame)
        for _ in range(n):
            h.update(next(digests))
    return h.hexdigest(), len(pieces)


def _legacy_checksum(arrays: dict) -> str:
    """The scheme of files written without ``"checksum_algo"``: one
    sha256 over every array's name, dtype, shape and bytes, in sorted
    key order.  The shape is that of ``np.ascontiguousarray``, as it was
    written: a 0-d leaf (AdamW's ``step``) hashes as ``(1,)``."""
    h = hashlib.sha256()
    for key in sorted(arrays):
        arr = np.ascontiguousarray(arrays[key])
        for part in (key, str(arr.dtype), str(arr.shape)):
            h.update(part.encode())
        h.update(_raw_bytes(arr))
    return h.hexdigest()


def _atomic_write(path: str, write_fn) -> None:
    d = os.path.dirname(os.path.abspath(path))
    fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
    os.close(fd)
    try:
        with open(tmp, "wb") as f:
            with tracing.span("checkpoint.write"):
                write_fn(f)
                f.flush()
            with tracing.span("checkpoint.fsync"):
                os.fsync(f.fileno())
        with tracing.span("checkpoint.rotate"):
            os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)


def save_checkpoint(path: str, tree: Any, metadata: Optional[dict] = None,
                    keep_previous: bool = True):
    """Atomically commit a pytree + metadata to ``path`` (.npz).

    Arrays and metadata land in ONE file published by ONE
    ``os.replace`` (the single commit point); the metadata carries a
    content checksum verified on load.  With ``keep_previous`` the
    outgoing checkpoint is rotated to ``path + ".prev"`` as the
    last-known-good fallback.
    """
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with tracing.span("checkpoint.fetch"):
        arrays = _flatten_with_paths(tree)
    tracing.count("checkpoint.bytes",
                  sum(a.nbytes for a in arrays.values()))
    meta = dict(metadata or {})
    with tracing.span("checkpoint.hash"):
        meta["checksum"], pieces = _content_checksum(arrays)
    meta["checksum_algo"] = CHECKSUM_ALGO
    tracing.count("checkpoint.hash_pieces", pieces)
    payload = dict(arrays)
    payload[META_KEY] = np.frombuffer(
        json.dumps(meta).encode(), dtype=np.uint8)
    if keep_previous and os.path.exists(path):
        with tracing.span("checkpoint.rotate"):
            os.replace(path, path + ".prev")
    _atomic_write(path, lambda f: np.savez(f, **payload))
    if metadata is not None:
        # convenience sidecar (atomic too); the bundled copy is
        # authoritative and load_metadata prefers it
        _atomic_write(path + ".meta.json",
                      lambda f: f.write(json.dumps(metadata).encode()))


def _read_bundle(path: str):
    """Load (arrays, bundled_meta_or_None); raises
    :class:`CheckpointCorruptError` on unreadable files or checksum
    mismatch.  Pre-checksum checkpoints (no bundled metadata) load
    without verification."""
    try:
        with np.load(path) as data:
            arrays = dict(data)
    except Exception as e:
        raise CheckpointCorruptError(
            f"checkpoint {path} is unreadable: {type(e).__name__}: {e}"
        ) from e
    meta = None
    raw = arrays.pop(META_KEY, None)
    if raw is not None:
        try:
            meta = json.loads(raw.tobytes().decode())
        except Exception as e:
            raise CheckpointCorruptError(
                f"checkpoint {path} has undecodable metadata: {e}") from e
        algo = meta.get("checksum_algo")
        if algo not in (CHECKSUM_ALGO, None):
            raise CheckpointCorruptError(
                f"checkpoint {path} names an unknown checksum scheme "
                f"{algo!r}")
        want = meta.get("checksum")
        if want is not None:
            got = (_content_checksum(arrays)[0] if algo == CHECKSUM_ALGO
                   else _legacy_checksum(arrays))
            if got != want:
                raise CheckpointCorruptError(
                    f"checkpoint {path} failed its content checksum")
    return arrays, meta


def verify_checkpoint(path: str) -> dict:
    """Integrity-check ``path`` without materializing a pytree; returns
    the bundled metadata ({} for pre-checksum files).  Raises
    :class:`CheckpointCorruptError` on corruption."""
    _, meta = _read_bundle(path)
    return meta or {}


def load_checkpoint(path: str, like: Any):
    """Restore into the structure of ``like`` (a pytree template),
    verifying the content checksum when present.  Each array lands
    where the template's leaf lives (its sharding, for a JAX array),
    never staged through the default device."""
    arrays, _ = _read_bundle(path)
    flat, treedef = jax.tree_util.tree_flatten_with_path(like)
    leaves = []
    for p, leaf in flat:
        key = "/".join(
            str(x.key) if hasattr(x, "key") else str(x.idx) for x in p)
        try:
            arr = arrays[key]
        except KeyError:
            raise CheckpointCorruptError(
                f"checkpoint {path} is missing array {key!r}") from None
        arr = np.asarray(arr, dtype=leaf.dtype)
        sharding = getattr(leaf, "sharding", None)
        leaves.append(jax.device_put(arr, sharding) if sharding is not None
                      else jax.numpy.asarray(arr))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def load_metadata(path: str) -> Optional[dict]:
    """Metadata for the checkpoint at ``path``: the bundled (atomic,
    checksummed) copy when present, else the legacy ``.meta.json``
    sidecar.  The internal checksum entries are stripped."""
    if os.path.exists(path):
        try:
            _, meta = _read_bundle(path)
        except CheckpointCorruptError:
            meta = None
        if meta is not None:
            return {k: v for k, v in meta.items()
                    if k not in ("checksum", "checksum_algo")}
    sidecar = path + ".meta.json"
    if os.path.exists(sidecar):
        with open(sidecar) as f:
            return json.load(f)
    return None


def load_training_state(path: str, params: Any, opt: Any):
    """Resume helper: restore ``(params, opt, start_step)`` from
    ``path`` if a checkpoint exists there, else return the inputs
    unchanged at step 0.

    Validates before trusting: a checkpoint that is unreadable or fails
    its content checksum is skipped with a recorded warning and the
    previous good checkpoint (``path + ".prev"``, rotated by
    :func:`save_checkpoint`) is tried instead; if that fails too, the
    run restarts from step 0 — never raises mid-run over a bad file.

    This is the single source of truth for the resume contract shared
    by ``LocalRunner.run_job`` and the execution-backend workers — the
    caller seeds fresh state, then continues from wherever the last
    run (or a preemption) checkpointed.
    """
    like = {"params": params, "opt": opt}
    for i, p in enumerate((path, path + ".prev")):
        if not os.path.exists(p):
            continue
        try:
            with tracing.span("restore.verify"):
                meta = verify_checkpoint(p)
            with tracing.span("restore.read"):
                state = load_checkpoint(p, like)
        except CheckpointCorruptError as e:
            warnings.warn(
                f"skipping corrupt checkpoint: {e}; "
                + ("falling back to previous good checkpoint"
                   if i == 0 else "restarting from step 0"),
                RuntimeWarning, stacklevel=2)
            continue
        if not meta:
            meta = load_metadata(p) or {}
        if i > 0:
            warnings.warn(
                f"resumed from previous good checkpoint {p} "
                f"(step {int(meta.get('step', 0))})",
                RuntimeWarning, stacklevel=2)
        return state["params"], state["opt"], int(meta.get("step", 0))
    return params, opt, 0
