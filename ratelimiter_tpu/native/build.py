"""Build the C++ extensions from the tracked sources, on demand.

A ``.so`` on disk is used only when it was built from exactly the bytes
of its sources: the build embeds a SHA-256 of them (``RL_SRC_HASH``,
returned by ``rl_*_src_hash()``) and the loader reads that marker out of
the file BEFORE dlopen — dlopen caches by pathname, so staleness is never
decided on a mapped object. A stale or foreign binary (the git-ignored
``.so`` files travel with a copied disk) is rebuilt in place; a failing
compile raises with the compiler's message.
"""

from __future__ import annotations

import ctypes
import hashlib
import importlib.util
import os
import re
import shutil
import subprocess
import sysconfig
from typing import Optional, Sequence

_MARK = re.compile(rb"RL_SRC_HASH:([0-9a-f]{64})")


class NativeBuildError(RuntimeError):
    """The compiler ran and refused the sources (carries its stderr)."""


def source_hash(sources: Sequence[str]) -> str:
    h = hashlib.sha256()
    for path in sources:
        with open(path, "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def built_hash(so: str) -> Optional[str]:
    """The source hash embedded in ``so``; None for a missing file or one
    built without the marker."""
    try:
        with open(so, "rb") as f:
            m = _MARK.search(f.read())
    except FileNotFoundError:
        return None
    return m.group(1).decode() if m else None


def ensure_built(so: str, sources: Sequence[str], *,
                 opt: str = "-O2") -> Optional[str]:
    """``so`` once it holds a build of exactly ``sources`` (the first is
    the translation unit, the rest are its headers); None when it does
    not and this host cannot build (no g++, or
    ``RATELIMITER_TPU_NO_BUILD=1``)."""
    want = source_hash(sources)
    if built_hash(so) == want:
        return so
    if (os.environ.get("RATELIMITER_TPU_NO_BUILD") == "1"
            or shutil.which("g++") is None):
        return None
    # Build beside the target and rename over it: concurrent builders
    # (test servers start in parallel) each publish a complete file, and
    # a process that already mapped the old inode keeps it.
    tmp = f"{so}.{os.getpid()}.tmp"
    try:
        proc = subprocess.run(
            ["g++", opt, "-shared", "-fPIC", "-std=c++17",
             f"-I{sysconfig.get_paths()['include']}",
             f'-DRL_SRC_HASH="{want}"', "-o", tmp, sources[0]],
            capture_output=True, text=True, timeout=300)
        if proc.returncode != 0:
            raise NativeBuildError(
                f"g++ could not build {os.path.basename(so)} from "
                f"{sources[0]}:\n{proc.stderr}")
        os.replace(tmp, so)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    return so


def load_extension(so: str, sources: Sequence[str], *, module: str,
                   abi_symbol: str, abi: int,
                   opt: str = "-O2") -> Optional[tuple]:
    """(ctypes handle, CPython extension module) of ``so`` built from
    exactly ``sources`` — both faces of the same file — or None on a host
    that cannot build it. ``abi_symbol`` is the C probe whose value the
    Python side was written against; compiler, loader and ABI errors
    propagate."""
    if ensure_built(so, sources, opt=opt) is None:
        return None
    lib = ctypes.CDLL(so)
    probe = getattr(lib, abi_symbol)
    probe.restype = ctypes.c_int64
    if probe() != abi:
        raise RuntimeError(
            f"{sources[0]} reports {abi_symbol}() == {probe()}, its "
            f"Python bridge expects {abi}")
    spec = importlib.util.spec_from_file_location(module, so)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return lib, mod
