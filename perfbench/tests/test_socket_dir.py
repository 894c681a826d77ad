"""The agent's unix socket stays inside the temporary directory the
run was given (or, where that is too deep for a socket, the run's
cache directory, HOME or the checkout); nowhere short enough is an
error with its reason, not a move to /tmp."""

import os
import tempfile

import pytest

import lib


def test_socket_dir_is_under_the_runs_tmpdir(tmp_path, monkeypatch):
    train = lib.load_driver("train")
    monkeypatch.setenv("TMPDIR", str(tmp_path))
    monkeypatch.setattr(tempfile, "tempdir", None)
    path = train.socket_dir("pb123")
    assert os.path.dirname(path) == str(tmp_path) and os.path.isdir(path)
    train.remove_job_files("pb123", path)
    assert not os.path.exists(path)


def test_a_tmpdir_too_deep_for_a_socket_is_refused(tmp_path, monkeypatch):
    train = lib.load_driver("train")
    deep = tmp_path / ("d" * 60) / ("e" * 60)
    deep.mkdir(parents=True)
    monkeypatch.setenv("TMPDIR", str(deep))
    monkeypatch.setattr(tempfile, "tempdir", None)
    # too deep for a socket: the run's cache directory is next
    monkeypatch.setenv("XDG_CACHE_HOME", str(tmp_path))
    path = train.socket_dir("pb123")
    assert os.path.dirname(path) == str(tmp_path)
    train.remove_job_files("pb123", path)
    # nowhere short enough: an error that says why, never /tmp
    monkeypatch.setenv("XDG_CACHE_HOME", str(deep))
    monkeypatch.setenv("HOME", str(deep))
    monkeypatch.setattr(lib, "ROOT", str(deep))
    with pytest.raises(RuntimeError, match="shorter TMPDIR"):
        train.socket_dir("pb123")
    assert os.listdir(deep) == []
