"""The order ``tests/conftest.py`` gives the collected files, and its table."""

from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
SECONDS = {"t/a.py": 900, "t/b.py": 500, "t/c.py": 400, "t/d.py": 350, "t/e.py": 300,
           "t/f.py": 250, "t/g.py": 200, "t/h.py": 100, "t/i.py": 61, "t/j.py": 59,
           "t/k.py": 30, "t/l.py": 6, "t/m.py": 5, "t/n.py": 4, "t/o.py": 3, "t/p.py": 2,
           "t/q.py": 1}


@pytest.fixture
def conftest(request):
    """``tests/conftest.py`` as pytest loaded it (two files of this tree are
    named ``conftest``, so not an import by name)."""
    return request.config.pluginmanager.get_plugin(str(REPO / "tests" / "conftest.py"))


def test_six_longest_then_six_lightest_then_the_rest_longest_first(conftest):
    files = sorted(SECONDS, reverse=True) + ["t/new.py"]  # the table lacks it: placed as 60 s
    assert conftest.order_files(files, SECONDS) == [
        "t/a.py", "t/b.py", "t/c.py", "t/d.py", "t/e.py", "t/f.py",
        "t/l.py", "t/m.py", "t/n.py", "t/o.py", "t/p.py", "t/q.py",
        "t/g.py", "t/h.py", "t/i.py", "t/new.py", "t/j.py", "t/k.py"]
    assert conftest.order_files(files[::-1], SECONDS) == conftest.order_files(files, SECONDS)
    # fewer files than two rounds of workers: longest first is all there is to say
    assert conftest.order_files(["t/q.py", "t/new.py", "t/a.py"], SECONDS) == [
        "t/a.py", "t/new.py", "t/q.py"]


def test_a_files_cases_stay_together_and_as_collected(conftest):
    ids = ["t/q.py::test_2", "t/q.py::test_1", "t/a.py::TestX::test_b", "t/a.py::test_c",
           "t/a.py::TestX::test_a", "t/new.py::test_z[1]", "t/new.py::test_z[0]"]
    order = conftest.order_items(ids, SECONDS)
    assert sorted(order) == list(range(len(ids)))  # every case once
    assert [ids[i] for i in order] == ids[2:5] + ids[5:] + ids[:2]


def test_every_file_of_the_committed_table_exists(conftest):
    table = conftest.file_seconds()
    assert table, "tests/file_seconds.json is empty"
    assert [f for f in table if not (REPO / f).is_file()] == []
    assert all(s >= 0 for s in table.values())
