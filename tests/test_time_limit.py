"""The per-test time limit of tests/conftest.py, tried on itself.

Each case runs `time_limit` in a child process with a sub-second limit passed
as an argument (the suite's own limit stays the constant in conftest.py): the
helper owns the process's one interval timer and faulthandler's one watchdog,
so it cannot be nested inside the limit this test itself runs under. No case
asserts on elapsed time; `subprocess.run`'s timeout only bounds a failure.
"""

import collections
import os
import subprocess
import sys
import types

import conftest

_PRELUDE = f"""
import signal, sys, threading, time
sys.path.insert(0, {os.path.dirname(os.path.abspath(__file__))!r})
import pytest
from conftest import time_limit

def parked_in_a_second_thread():
    time.sleep(3600)

threading.Thread(target=parked_in_a_second_thread, daemon=True).start()
"""


def _child(body):
    return subprocess.run(
        [sys.executable, "-c", _PRELUDE + body],
        capture_output=True, text=True, timeout=200,
    )


def test_python_wait_past_the_limit_fails_by_name_and_leaves_stacks():
    done = _child("""
def waits_for_ever():
    threading.Event().wait()

try:
    with time_limit("tests/test_x.py::test_waits", 0.3, 3600.0, 2):
        waits_for_ever()
except pytest.fail.Exception as failure:
    print("failed:", failure.msg)
print("disarmed:", signal.getitimer(signal.ITIMER_REAL), signal.getsignal(signal.SIGALRM) == signal.SIG_DFL)
""")
    assert done.returncode == 0, done.stderr
    assert "failed: tests/test_x.py::test_waits exceeded 0.3 s" in done.stdout
    assert "disarmed: (0.0, 0.0) True" in done.stdout
    # Every thread's stack, the waiting main thread's and the bystander's.
    assert "waits_for_ever" in done.stderr, done.stderr
    assert "parked_in_a_second_thread" in done.stderr, done.stderr


def test_wait_that_blocks_the_signal_is_ended_by_the_backstop():
    done = _child("""
def waits_inside_c():
    signal.pthread_sigmask(signal.SIG_BLOCK, {signal.SIGALRM})
    time.sleep(3600)

with time_limit("tests/test_x.py::test_waits", 0.2, 0.2, 2):
    waits_inside_c()
print("never reached")
""")
    assert done.returncode != 0
    assert "never reached" not in done.stdout
    assert "Timeout (0:00:00" in done.stderr, done.stderr
    assert "waits_inside_c" in done.stderr, done.stderr
    assert "parked_in_a_second_thread" in done.stderr, done.stderr


def test_inside_the_limit_nothing_happens_and_both_timers_are_disarmed():
    done = _child("""
def on_alarm(signum, frame):
    raise AssertionError("the timer outlived its test")

signal.signal(signal.SIGALRM, on_alarm)
with time_limit("tests/test_x.py::test_quick", 0.2, 0.2, 2):
    print("ran")
print("disarmed:", signal.getitimer(signal.ITIMER_REAL), signal.getsignal(signal.SIGALRM) is on_alarm)
time.sleep(1.0)  # well past limit and grace: either timer left armed would fire here
print("alive")
""")
    assert done.returncode == 0, done.stderr
    assert done.stdout.split("\n")[:3] == ["ran", "disarmed: (0.0, 0.0) True", "alive"]
    assert "most recent call first" not in done.stderr, done.stderr


def test_a_test_ended_by_the_backstop_is_not_run_again():
    """xdist's loadfile puts a crashed worker's file back on its queue whole;
    the hook marks the test that crashed as done, so the rest of the file runs
    on the new worker and the hang does not."""
    sched = types.SimpleNamespace(workqueue=collections.OrderedDict({
        "tests/a.py": {"tests/a.py::one": True, "tests/a.py::two": False, "tests/a.py::three": False},
        "tests/b.py": {"tests/b.py::only": False},
    }))
    conftest.pytest_handlecrashitem("tests/a.py::two", None, sched)
    assert sched.workqueue["tests/a.py"] == {
        "tests/a.py::one": True, "tests/a.py::two": True, "tests/a.py::three": False,
    }
    conftest.pytest_handlecrashitem("tests/b.py::only", None, sched)
    assert list(sched.workqueue) == ["tests/a.py"]
