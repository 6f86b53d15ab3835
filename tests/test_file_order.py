"""The order ``tests/conftest.py`` gives the collected files, its table, and
the limit it gives every case."""

import re
import signal
import time
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


def _timer_is_off():
    return signal.getitimer(signal.ITIMER_REAL) == (0.0, 0.0)


def test_a_case_that_outruns_its_limit_fails_with_its_own_name(conftest, monkeypatch):
    monkeypatch.setattr(conftest, "CASE_LIMIT_S", 1.0)
    before, t0 = signal.getsignal(signal.SIGALRM), time.monotonic()
    with pytest.raises(pytest.fail.Exception,
                       match=r"^t/a\.py::test_waits\[8k\] ran past its limit of 1 s$"):
        with conftest.case_limit("t/a.py::test_waits[8k]"):
            time.sleep(30)  # what waits here waits in Python: the handler cuts it
    assert 0.9 < time.monotonic() - t0 < 10
    # ... and only that case: the next starts with no timer and the former handler
    assert _timer_is_off() and signal.getsignal(signal.SIGALRM) is before


def test_a_case_inside_its_limit_is_untouched_and_leaves_no_timer(conftest, monkeypatch):
    monkeypatch.setattr(conftest, "CASE_LIMIT_S", 1.0)
    before = signal.getsignal(signal.SIGALRM)
    with conftest.case_limit("t/a.py::test_quick"):
        assert 0 < signal.getitimer(signal.ITIMER_REAL)[0] <= 1.0
        assert signal.getsignal(signal.SIGALRM) is not before
    assert _timer_is_off() and signal.getsignal(signal.SIGALRM) is before


def test_every_case_runs_under_the_limit_and_its_alarm_names_it(conftest, request):
    """This case, as any other: the autouse fixture armed the timer with the
    one constant and a handler that fails the case by its node id."""
    assert conftest.CASE_LIMIT_S == 600.0
    assert 0 < signal.getitimer(signal.ITIMER_REAL)[0] <= conftest.CASE_LIMIT_S
    with pytest.raises(pytest.fail.Exception, match=re.escape(request.node.nodeid) + " ran past"):
        signal.getsignal(signal.SIGALRM)(signal.SIGALRM, None)
