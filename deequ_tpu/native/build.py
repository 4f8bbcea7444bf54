"""Build the native host-kernel shared library.

Usage: ``python -m deequ_tpu.native.build``; `lib.py` also invokes this
automatically on first use (set DEEQU_TPU_NO_NATIVE=1 to disable).

The library is compiled with ``-march=native``, so it belongs to the source
it was built from AND to the CPU it was built on. Its file name carries a
digest of both: a checkout copied to another machine, or a changed source,
finds no library under its own name and builds one, and never loads a
library built for something else.
"""

from __future__ import annotations

import hashlib
import os
import platform
import subprocess
import sys

_DIR = os.path.dirname(os.path.abspath(__file__))
SOURCE = os.path.join(_DIR, "src", "host_kernels.cpp")
COMPILE_FLAGS = ("-O3", "-march=native", "-shared", "-fPIC", "-pthread")


def host_key() -> str:
    """What ``-march=native`` compiles for: the machine and, on Linux, the
    CPU model and its feature flags."""
    parts = [platform.machine(), platform.processor()]
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("model name", "flags", "Features")):
                    parts.append(line.strip())
                if len(parts) >= 4:
                    break
    except OSError:
        pass
    return "\n".join(parts)


def library_path(host: str | None = None) -> str:
    """The library's path for the source's content and this host (or
    ``host``)."""
    h = hashlib.sha256()
    with open(SOURCE, "rb") as f:
        h.update(f.read())
    h.update(" ".join(COMPILE_FLAGS).encode())
    h.update((host_key() if host is None else host).encode())
    return os.path.join(_DIR, f"_host_kernels-{h.hexdigest()[:16]}.so")


def build(force: bool = False) -> str:
    """Compile the shared library unless this source+host already has one;
    returns its path."""
    library = library_path()
    if not force and os.path.exists(library):
        return library
    # compile to a temp path and rename into place so concurrent importers
    # never dlopen a half-written library
    tmp = f"{library}.{os.getpid()}.tmp"
    cmd = ["g++", *COMPILE_FLAGS, "-o", tmp, SOURCE, "-ldl"]
    try:
        result = subprocess.run(cmd, capture_output=True, text=True)
        if result.returncode != 0:
            raise RuntimeError(f"native build failed:\n{result.stderr}")
        os.replace(tmp, library)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    return library


if __name__ == "__main__":
    path = build(force="--force" in sys.argv)
    print(f"built {path}")
