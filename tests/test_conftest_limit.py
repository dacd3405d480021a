"""The per-test time limit of conftest.py, driven through its own helper."""

import asyncio
import signal
import threading
import time

import pytest

from tests.conftest import time_limit


@pytest.mark.parametrize("hang", [
    lambda: asyncio.run(asyncio.sleep(5)),
    lambda: time.sleep(5),
], ids=["asyncio_run", "sleep"])
def test_time_limit_fails_a_hang_and_disarms(hang, capfd):
    t0 = time.monotonic()
    with pytest.raises(pytest.fail.Exception, match="exceeded 0.2 s"):
        with time_limit(0.2):
            hang()
    assert time.monotonic() - t0 < 1.0
    assert signal.getitimer(signal.ITIMER_REAL) == (0.0, 0.0)
    assert "test_conftest_limit.py" in capfd.readouterr().err, \
        "no stack dump names where the test hung"


def test_time_limit_disarms_after_a_test_that_ends():
    with time_limit(0.2):
        pass
    assert signal.getitimer(signal.ITIMER_REAL) == (0.0, 0.0)
    time.sleep(0.3)   # nothing left to fire


def test_time_limit_is_not_armed_off_the_main_thread():
    left = []

    def body():
        with time_limit(0.2):   # signal.signal() would raise here
            left.append(signal.getitimer(signal.ITIMER_REAL)[0])

    t = threading.Thread(target=body)
    t.start()
    t.join()
    # still this test's own limit (the autouse fixture's), not 0.2 s
    assert len(left) == 1 and left[0] > 1.0
