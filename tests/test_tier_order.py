"""The order in which tier-1's files are handed out (``tests/conftest.py``
``_tier_order``): the files that hold a long case first, every other file
by descending count of cases, as xdist's ``loadfile`` did by itself before
the conftest took its reorder away. No cluster, nothing compiled."""

from __future__ import annotations

import os
import pathlib
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture
def conftest(pytestconfig):
    """``tests/conftest.py`` as pytest loaded it (``import conftest`` may
    give ``tests/chipbench/``'s: a conftest is registered by its path)."""
    return pytestconfig.pluginmanager.get_plugin(
        os.path.join(HERE, "conftest.py"))


def _items(*files):
    """Hand-made items, one a (file, case): all the order reads is
    ``path``."""
    return [types.SimpleNamespace(path=pathlib.Path(HERE, name),
                                  case=f"{name}::{i}")
            for i, name in enumerate(files)]


def test_every_first_file_is_a_file_under_tests(conftest):
    """A rename must not send a long file silently to the back."""
    assert conftest._FIRST_FILES, "the rehearsal at least"
    assert len(set(conftest._FIRST_FILES)) == len(conftest._FIRST_FILES)
    for name in conftest._FIRST_FILES:
        assert os.path.isfile(os.path.join(HERE, name)), name
    assert conftest._FIRST_FILES[0] == "chipbench/test_chipbench_rehearsal.py"
    # one worker a first file at the start, in the driver's form (-n 6)
    assert len(conftest._FIRST_FILES) <= 6


def test_the_conftest_takes_xdists_own_reorder_away(pytestconfig):
    """With it on, xdist sorts the files by count again and the order
    below is lost; the driver's command does not pass the flag."""
    assert pytestconfig.option.loadscopereorder is False


def test_first_files_first_then_by_descending_count_stably(conftest,
                                                           monkeypatch):
    monkeypatch.setattr(conftest, "_FIRST_FILES",
                        ("sub/test_long.py", "test_longish.py"))
    items = _items(
        "test_two.py", "test_three.py", "test_longish.py", "test_two.py",
        "test_also_two.py", "sub/test_long.py", "test_three.py",
        "test_also_two.py", "test_three.py", "test_one.py")
    got = [item.case for item in conftest._tier_order(items)]
    assert got == [
        "sub/test_long.py::5",          # first files in the tuple's order,
        "test_longish.py::2",           # whatever their counts
        "test_three.py::1", "test_three.py::6", "test_three.py::8",
        "test_two.py::0", "test_two.py::3",     # equal counts: as collected
        "test_also_two.py::4", "test_also_two.py::7",
        "test_one.py::9"]
    assert conftest._tier_order([]) == []
    # a file of the same name elsewhere under tests/ is not a first file
    other = _items("test_one.py", "test_one.py", "other/test_long.py")
    assert [item.case for item in conftest._tier_order(other)] == [
        "test_one.py::0", "test_one.py::1", "other/test_long.py::2"]


def test_this_runs_order_is_the_conftests(conftest, request):
    """The session's own items, as ``-m`` and ``-k`` left them, are in the
    conftest's order: the hook ran after the deselection, not before."""
    items = request.session.items
    assert [item.nodeid for item in conftest._tier_order(items)] == [
        item.nodeid for item in items]
