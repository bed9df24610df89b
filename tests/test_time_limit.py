"""The one time limit every test runs under (``tests/conftest.py``)."""
import signal
import time

import pytest

from conftest import LIMIT, time_limit


@pytest.mark.skipif(not hasattr(signal, "SIGALRM"), reason="no SIGALRM")
def test_time_limit_fails_the_test_by_name_and_disarms():
    t0 = time.monotonic()
    with pytest.raises(pytest.fail.Exception, match="test_that_hangs") as err:
        with time_limit(1, "tests/test_x.py::test_that_hangs"):
            time.sleep(3)
    assert time.monotonic() - t0 < 2.5          # the sleep was cut short
    assert "time limit of 1 s" in str(err.value)

    # disarmed: what runs on is this test's own limit, armed by the autouse
    # fixture before the call, not the 1 s one
    left, _ = signal.getitimer(signal.ITIMER_REAL)
    assert LIMIT - 30 < left <= LIMIT
    assert signal.getsignal(signal.SIGALRM) is not signal.SIG_DFL

    # a second call, which ends in time: no failure during or after it
    with time_limit(1, "tests/test_x.py::test_in_time"):
        pass
    time.sleep(1.2)
    left, _ = signal.getitimer(signal.ITIMER_REAL)
    assert LIMIT - 30 < left <= LIMIT
