"""net/harness.py open_loop times a request from the instant it was DUE:
a generator that runs late (here: the loop is blocked for 0.3 s) charges
its lateness to the requests that were due meanwhile."""

import asyncio
import time

from accord_tpu.net.harness import open_loop


class _StubClient:
    """Answers at once; its first call blocks the loop, as a stalled
    server sharing the generator's process does."""

    def __init__(self):
        self.calls = 0

    async def submit(self, ops, timeout=None):
        self.calls += 1
        if self.calls == 1:
            time.sleep(0.3)
        return {"type": "txn_ok", "txn": ops}


def test_a_late_started_request_is_charged_its_lateness():
    client = _StubClient()
    res = asyncio.run(open_loop(client, rate=200.0, duration=0.5, seed=4))
    assert res.ok == res.sent == client.calls > 20
    lat = sorted(res.latencies_ms)
    # every submit but the first returned at once: timed from when its
    # task got the loop, all but one latency would be about zero.  About
    # 60 requests came due inside the stall; the earliest of them waited
    # nearly all of it
    late = [ms for ms in lat if ms > 50.0]
    assert len(late) >= 20, lat
    assert 250.0 <= lat[-1] <= 1_000.0
    assert lat[0] < 50.0                     # those due after it did not
