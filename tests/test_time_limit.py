"""The per-test limit of ``tests/conftest.py``: a body that waits past its
limit fails by name with every thread's stack, a body inside it is left
alone, and the timers are handed back afterwards."""
import glob
import os
import re
import signal
import threading
import time

import pytest

from conftest import TEST_LIMIT_S, time_limit


def test_a_body_that_waits_past_its_limit_fails_with_every_stack():
    release = threading.Event()
    helper = threading.Thread(target=release.wait, args=(30.0,),
                              name="held-helper")
    helper.start()
    t0 = time.monotonic()
    try:
        with pytest.raises(pytest.fail.Exception) as failure:
            with time_limit(0.5):
                threading.Event().wait(30.0)      # the wait nothing ends
    finally:
        release.set()
        helper.join(10.0)
    assert not helper.is_alive()
    assert time.monotonic() - t0 < 30.0     # the limit ended it, not the wait
    report = str(failure.value)
    assert "limit of 0.5 s" in report
    # the waiting line of this thread and the other thread, by name
    assert "threading.Event().wait(30.0)" in report
    assert "held-helper" in report


def test_a_body_inside_its_limit_is_untouched():
    with time_limit(5.0):
        total = sum(range(1000))
    assert total == 499500


def test_the_timers_go_back_to_the_enclosing_limit():
    # this test runs under the protocol's own limit: after an inner limit
    # ends, the alarm counts down to the outer deadline again
    outer_before = signal.getitimer(signal.ITIMER_REAL)[0]
    assert 0.0 < outer_before <= TEST_LIMIT_S
    with time_limit(2.0):
        assert 0.0 < signal.getitimer(signal.ITIMER_REAL)[0] <= 2.0
    outer_after = signal.getitimer(signal.ITIMER_REAL)[0]
    assert 2.0 < outer_after <= outer_before
    # and an expired inner limit leaves no alarm behind to hit a later test
    with pytest.raises(pytest.fail.Exception):
        with time_limit(0.05):
            time.sleep(5.0)
    time.sleep(0.2)
    assert signal.getitimer(signal.ITIMER_REAL)[0] > 2.0


def test_every_wait_in_tests_is_shorter_than_the_limit():
    """The order that makes a wait fail by its own assertion first."""
    here = os.path.dirname(os.path.abspath(__file__))
    too_long = []
    for path in sorted(glob.glob(os.path.join(here, "*.py"))):
        for n, line in enumerate(open(path), 1):
            for m in re.finditer(r"timeout(?:_s)?=([0-9][0-9_.]*)", line):
                if float(m.group(1)) >= TEST_LIMIT_S:
                    too_long.append(f"{os.path.basename(path)}:{n}: {line.strip()}")
    assert too_long == []
