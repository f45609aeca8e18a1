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
- A sha256 content checksum over every array (name, dtype, shape,
  bytes) is stored in the bundled metadata and verified by
  :func:`load_checkpoint`; mismatch raises
  :class:`CheckpointCorruptError`.
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
from typing import Any, Optional

import jax
import numpy as np

from .. import tracing

# npz entry under which the JSON metadata (incl. checksum) is bundled;
# the name cannot collide with pytree paths (they never start with "__")
META_KEY = "__saturn_meta__"


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


def _content_checksum(arrays: dict) -> str:
    """sha256 over every array's (name, dtype, shape, bytes), in sorted
    key order — invariant to npz member ordering."""
    h = hashlib.sha256()
    for key in sorted(arrays):
        arr = np.ascontiguousarray(arrays[key])
        h.update(key.encode())
        h.update(str(arr.dtype).encode())
        h.update(str(arr.shape).encode())
        h.update(arr.tobytes())
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
        meta["checksum"] = _content_checksum(arrays)
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
        want = meta.get("checksum")
        if want is not None and _content_checksum(arrays) != want:
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
    sidecar.  The internal checksum entry is stripped."""
    if os.path.exists(path):
        try:
            _, meta = _read_bundle(path)
        except CheckpointCorruptError:
            meta = None
        if meta is not None:
            return {k: v for k, v in meta.items() if k != "checksum"}
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
