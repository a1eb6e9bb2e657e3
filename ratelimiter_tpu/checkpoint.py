"""Checkpoint / restore of limiter state.

The reference gets durability for free: state lives server-side in Redis
and outlives the Go process, bounded by TTLs (``fixedwindow.go:151``,
``docs/ADR/001:51-52`` — losing Redis loses all counters). Here state
lives in HBM and dies with the process, so snapshot/restore is explicit
(SURVEY.md §5.4).

Format: one ``.npz`` holding the state arrays plus a JSON header with a
format version, a backend kind tag, and a **config fingerprint** — restore
refuses a snapshot taken under a different algorithm/limit/window/geometry
(the arrays would be reinterpreted silently otherwise).

Staleness semantics (documented contract, tested in
tests/test_checkpoint.py):

* decisions made after the snapshot are lost on restore — the restored
  limiter *under*-counts the crash window, so errors are toward ALLOWING,
  exactly the reference's "losing Redis = losing counters" posture and
  the right direction for availability;
* elapsed wall time between save and restore needs no special handling:
  every backend keys its state off absolute host timestamps, so the first
  post-restore dispatch applies the usual catch-up (sketch: sub-window
  rollover sweep masks out expired slabs; token bucket: decay/refill from
  the restored ``last``; dense/exact windows: lazy window roll). A
  snapshot restored after >= 1 full window therefore behaves like a fresh
  limiter, as it must.
"""

from __future__ import annotations

import hashlib
import itertools
import io
import json
import os
from dataclasses import asdict
from typing import Any, Dict, Tuple

import numpy as np

from ratelimiter_tpu.core.config import Config, DenseParams
from ratelimiter_tpu.core.errors import CheckpointError

FORMAT_VERSION = 1
_META_KEY = "__ratelimiter_tpu_meta__"
_tmp_counter = itertools.count()


def config_fingerprint(config: Config) -> str:
    """Stable hash over every semantic config field (dataclass fields are
    all plain values, so the sorted-JSON of asdict is canonical).

    The ``persistence`` spec is excluded: snapshot cadence / fsync policy
    are operational knobs, and a snapshot taken at one cadence must
    restore under another. ``mesh`` (slice-parallel placement, ADR-012)
    is excluded too: the device count is where state lives, not what it means — the
    per-slice-count refusal lives in SlicedMeshLimiter.restore, which
    can NAME the mismatch instead of reporting an opaque fingerprint
    diff. Every OTHER field participates — changing this function's
    output strands every existing snapshot, which is why
    tests/test_checkpoint.py pins a golden value.
    """
    fields = asdict(config)
    fields.pop("persistence", None)
    fields.pop("mesh", None)
    h = fields.get("hierarchy")
    if isinstance(h, dict) and not h.get("tenants"):
        # Hierarchy disabled is the pre-ADR-020 world: dropping the spec
        # keeps every existing snapshot's fingerprint (golden pinned).
        # When ENABLED, the cascade geometry shapes the tn_* state
        # arrays, so it must participate like any other geometry field.
        fields.pop("hierarchy", None)
    d = fields.get("dense")
    if isinstance(d, dict):
        # The directory's geometry (ADR-027) at its defaults is the world
        # before it: dropped, so that no sketch or exact snapshot is
        # stranded by two fields they never read. Set otherwise, it
        # decides where an entry sits and participates.
        for name, default in (("lanes", DenseParams.lanes),
                              ("probe_bound", DenseParams.probe_bound)):
            if d.get(name) == default:
                d.pop(name)
    payload = json.dumps(
        {**fields, "algorithm": str(config.algorithm)},
        sort_keys=True, default=str)
    return hashlib.sha256(payload.encode()).hexdigest()[:32]


def fsync_dir(path: str) -> None:
    """fsync a directory so a just-renamed entry survives power loss
    (the rename itself lives in the directory's metadata). Best-effort:
    some filesystems/platforms refuse O_RDONLY fsync on directories."""
    try:
        fd = os.open(path if path else ".", os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass
    finally:
        os.close(fd)


def write_atomic(path: str, data: bytes) -> None:
    """Crash-atomic file write: tmp + fsync(file) + os.replace + fsync(dir).
    A crash at ANY point leaves either the old file or the new one, never
    a torn mix; after return the bytes are on stable storage."""
    # Unique per call, not just per process: concurrent writers to the
    # same path would otherwise share one tmp name and steal each
    # other's file out from under os.replace (last replace wins either
    # way; both must survive).
    tmp = f"{path}.tmp.{os.getpid()}.{next(_tmp_counter)}"
    try:
        with open(tmp, "wb") as f:
            f.write(data)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    fsync_dir(os.path.dirname(os.path.abspath(path)))


def save_state(path: str, kind: str, config: Config,
               arrays: Dict[str, np.ndarray], extra: Dict[str, Any]) -> None:
    """Crash-atomic snapshot write (see write_atomic): a crash mid-save
    never corrupts the previous snapshot, and a completed save survives
    power loss (file and directory entry both fsynced)."""
    meta = {
        "format_version": FORMAT_VERSION,
        "kind": kind,
        "config_fingerprint": config_fingerprint(config),
        **extra,
    }
    if _META_KEY in arrays:
        raise CheckpointError(f"array name {_META_KEY!r} is reserved")
    buf = io.BytesIO()
    np.savez(buf, **arrays,
             **{_META_KEY: np.frombuffer(
                 json.dumps(meta).encode(), dtype=np.uint8)})
    write_atomic(path, buf.getvalue())


def load_state(path: str, kind: str, config: Config,
               ) -> Tuple[Dict[str, np.ndarray], Dict[str, Any]]:
    """Load + validate a snapshot for the given limiter kind and config."""
    with np.load(path, allow_pickle=False) as z:
        arrays = {k: z[k] for k in z.files if k != _META_KEY}
        if _META_KEY not in z.files:
            raise CheckpointError(f"{path}: not a ratelimiter_tpu checkpoint")
        meta = json.loads(bytes(z[_META_KEY]).decode())
    if meta.get("format_version") != FORMAT_VERSION:
        raise CheckpointError(
            f"{path}: format version {meta.get('format_version')} != "
            f"{FORMAT_VERSION}")
    if meta.get("kind") != kind:
        raise CheckpointError(
            f"{path}: snapshot kind {meta.get('kind')!r} cannot restore a "
            f"{kind!r} limiter")
    fp = config_fingerprint(config)
    if meta.get("config_fingerprint") != fp:
        raise CheckpointError(
            f"{path}: config fingerprint mismatch — snapshot was taken "
            "under a different algorithm/limit/window/geometry")
    return arrays, meta
