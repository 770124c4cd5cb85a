"""Shared build-on-first-use helper for the src/ native extensions.

One implementation of the pattern every ctypes binding uses (shm_store,
cgroup, rpcframe): the shared object is a build product, never committed —
it is compiled with g++ the first time a checkout needs it and again
whenever its source no longer reads as it did at the last build.  Staleness
is decided from the source's CONTENT (a digest kept beside the binary),
because a copy of the tree promises nothing about mtimes.  Builds write to a
`.tmp<pid>` file and `os.replace` into place so concurrent processes race
safely.  See src/README.md for the build rules.
"""

from __future__ import annotations

import hashlib
import os
import subprocess

CXX_FLAGS = ["-O2", "-fPIC", "-shared", "-std=c++17"]


def _build_cmd(src: str, out: str, ldflags: tuple) -> list:
    return ["g++", *CXX_FLAGS, "-o", out, src, *ldflags]


def source_digest(src: str, ldflags: tuple = ()) -> str:
    """What a binary must have been built from to count as current: the
    source's bytes and the compile line."""
    with open(src, "rb") as f:
        return hashlib.sha256(
            " ".join(_build_cmd(src, "", ldflags)).encode() + b"\0"
            + f.read()).hexdigest()


def build_so(src: str, so: str, ldflags: tuple = ()) -> str:
    """Ensure `so` is what `src` compiles to now; returns the path.  A
    failed build raises (no compiler, compile error): a binary that does
    not match its source is never loaded.  Callers serialize via their own
    module lock; this function only does the filesystem dance."""
    digest = source_digest(src, ldflags)
    stamp = so + ".sha256"
    try:
        with open(stamp) as f:
            if f.read().strip() == digest and os.path.exists(so):
                return so
    except FileNotFoundError:
        pass
    tmp = so + f".tmp{os.getpid()}"
    try:
        subprocess.run(_build_cmd(src, tmp, ldflags), check=True,
                       capture_output=True)
        os.replace(tmp, so)
        with open(tmp, "w") as f:
            f.write(digest + "\n")
        os.replace(tmp, stamp)
    finally:
        try:
            os.unlink(tmp)
        except FileNotFoundError:
            pass
    return so
