"""The native library is built from what git commits: its file name
carries a hash of the C++ sources and flags, so an edited source builds
a new library, an unchanged one is reused whatever the mtimes say, and
no binary is tracked."""

import ctypes
import os
import subprocess

import pytest

from mythril_tpu import native

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _write_src(src_dir, answer: int) -> None:
    with open(os.path.join(src_dir, "answer.cpp"), "w") as fh:
        fh.write(f'extern "C" int answer() {{ return {answer}; }}\n')


def _answer(path: str) -> int:
    return ctypes.CDLL(path).answer()


def test_rebuilt_when_the_source_hash_changes_and_reused_otherwise(
    tmp_path, monkeypatch
):
    src = tmp_path / "csrc"
    src.mkdir()
    _write_src(src, 41)
    first = native.build(str(src))
    assert _answer(first) == 41

    # same sources: no compiler call, same library — even when the
    # sources look newer than the binary (a copied tree)
    os.utime(src / "answer.cpp")

    def no_compile(*args, **kwargs):
        raise AssertionError("rebuilt an unchanged source tree")

    monkeypatch.setattr(native.subprocess, "run", no_compile)
    assert native.build(str(src)) == first
    monkeypatch.undo()

    _write_src(src, 42)
    second = native.build(str(src))
    assert second != first
    assert _answer(second) == 42
    assert not os.path.exists(first), "stale library left behind"


def test_the_shipped_library_is_named_by_its_source_hash():
    native.load()
    assert os.path.exists(native.lib_path())
    assert os.path.basename(native.lib_path()).startswith("_native.")


def test_git_tracks_no_shared_library():
    try:
        listed = subprocess.run(
            ["git", "ls-files", "*.so"], cwd=REPO, capture_output=True,
            text=True, timeout=30, check=True,
        ).stdout.split()
    except (OSError, subprocess.CalledProcessError):
        pytest.skip("not a git checkout")
    assert listed == []
