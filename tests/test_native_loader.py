"""``utils/native.py`` builds the host kernels where they run: staleness
is decided by the content of csrc/ (a copied tree scrambles mtimes), and
a toolchain that runs and fails is an error, not a silent numpy fallback."""

import os
import shutil

import pytest

import bigdl_tpu.utils.native as native

pytestmark = pytest.mark.skipif(
    shutil.which("make") is None or shutil.which("g++") is None,
    reason="no C++ toolchain")


@pytest.fixture
def scratch_csrc(tmp_path, monkeypatch):
    csrc = tmp_path / "csrc"
    csrc.mkdir()
    for name in ("bigdl_tpu_native.cpp", "Makefile"):
        shutil.copy(os.path.join(native._CSRC, name), csrc / name)
    so = str(csrc / "libbigdl_tpu_native.so")
    monkeypatch.setattr(native, "_CSRC", str(csrc))
    monkeypatch.setattr(native, "_SO", so)
    monkeypatch.setattr(native, "_STAMP", so + ".sha256")
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", False)
    return csrc


def test_builds_from_source_and_rebuilds_on_content_change(scratch_csrc,
                                                           monkeypatch):
    lib = native.native_lib()
    assert lib is not None and lib.crc32c_bytes(b"123456789") == 0xE3069283
    built = os.stat(native._SO).st_mtime_ns
    # same content, scrambled mtimes (what a copy does): no rebuild
    os.utime(scratch_csrc / "bigdl_tpu_native.cpp", ns=(built * 2, built * 2))
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", False)
    assert native.native_lib() is not None
    assert os.stat(native._SO).st_mtime_ns == built
    # a binary built from other source is not loaded: it is rebuilt
    with open(scratch_csrc / "bigdl_tpu_native.cpp", "a") as f:
        f.write("\n// changed\n")
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", False)
    assert native.native_lib() is not None
    assert os.stat(native._SO).st_mtime_ns != built


def test_failed_build_is_an_error(scratch_csrc):
    (scratch_csrc / "bigdl_tpu_native.cpp").write_text("this is not C++\n")
    with pytest.raises(RuntimeError, match="failed"):
        native.native_lib()
