"""The native host library is keyed on its source's content and on the host
it was compiled for (-march=native): a library built for another source or
another CPU is rebuilt, never loaded as is."""

import ctypes
import os
import shutil

import pytest

from deequ_tpu.native import build as native_build


@pytest.fixture
def private_build(tmp_path, monkeypatch):
    """build.py pointed at a private copy of the source and output dir."""
    src = tmp_path / "host_kernels.cpp"
    shutil.copy(native_build.SOURCE, src)
    monkeypatch.setattr(native_build, "_DIR", str(tmp_path))
    monkeypatch.setattr(native_build, "SOURCE", str(src))
    return src


def test_path_keys_on_source_and_host(private_build):
    here = native_build.library_path()
    assert native_build.library_path(host="another-cpu") != here
    private_build.write_text(private_build.read_text() + "\n// edited\n")
    assert native_build.library_path() != here


def test_foreign_library_is_rebuilt_not_loaded(private_build):
    # a library copied in from another machine sits under that machine's key
    foreign = native_build.library_path(host="another-cpu")
    with open(foreign, "wb") as f:
        f.write(b"not a library for this host")
    built = native_build.build()
    assert built != foreign
    assert built == native_build.library_path()
    ctypes.CDLL(built)  # loads: it was compiled here
    with open(foreign, "rb") as f:
        assert f.read() == b"not a library for this host"


def test_changed_source_rebuilds(private_build):
    first = native_build.build()
    mtime = os.path.getmtime(first)
    assert native_build.build() == first  # same source + host: reused
    assert os.path.getmtime(first) == mtime
    private_build.write_text(private_build.read_text() + "\n// edited\n")
    second = native_build.build()
    assert second != first and os.path.exists(second)
    ctypes.CDLL(second)
