"""Build the native host libraries from their committed sources.

Each library lands at ``native/build/lib<name>-<key>.so``, where ``key``
hashes the ``.cpp`` source together with the compiler and its flags, so
a library built from other sources or other flags is never loaded.  The
flags name no host CPU (no ``-march=native``): a library built on one
x86-64 machine loads on another, and SHA-NI is dispatched at run time
(``sha256_have_shani``).  Parallel builders (test workers) each compile
to a temporary name and rename atomically.
"""
from __future__ import annotations

import hashlib
import os
import subprocess
import tempfile
from pathlib import Path

NATIVE = Path(__file__).resolve().parents[2] / "native"
CXX = "g++"
_COMMON = ("-std=c++17", "-shared", "-fPIC")

#: library name -> (source file, compiler flags)
LIBS = {
    "kvstore": ("kvstore.cpp", ("-O2",) + _COMMON),
    "sha256host": ("sha256_host.cpp", ("-O3",) + _COMMON + ("-pthread",)),
    "bls12381": ("bls12_381.cpp", ("-O3",) + _COMMON + ("-pthread",)),
}


class BuildError(RuntimeError):
    """The library cannot be built here (no compiler, or it refused)."""


def library(name: str) -> Path:
    """Path of the library built from the current source; builds it on
    first use."""
    src, flags = LIBS[name]
    cpp = NATIVE / src
    key = hashlib.sha256(
        cpp.read_bytes() + "\0".join((CXX,) + flags).encode()
    ).hexdigest()[:16]
    out = NATIVE / "build" / f"lib{name}-{key}.so"
    if out.exists():
        return out
    out.parent.mkdir(exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=out.parent, prefix=out.name + ".",
                               suffix=".tmp")
    os.close(fd)
    try:
        try:
            proc = subprocess.run([CXX, *flags, "-o", tmp, str(cpp)],
                                  capture_output=True, text=True)
        except OSError as exc:          # no compiler on this machine
            raise BuildError(f"cannot run {CXX} for {src}: {exc}") from exc
        if proc.returncode != 0:
            raise BuildError(f"{CXX} failed on {src}: {proc.stderr[-2000:]}")
        os.replace(tmp, out)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    return out
