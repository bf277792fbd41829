"""The window's arithmetic: tokens of requests still in flight count, tails
are over every request, time to first token runs from the scheduled
arrival.  A stand-in server with fixed service times drives the loop."""
import math
import time
from collections import deque

import numpy as np

from bench.lib import readings, serve, traffic

ADMIT_S, STEP_S = 0.02, 0.004


class FakeServer:
    """The online entries of ``ContinuousServer``, with fixed times."""

    def __init__(self, slots):
        self.slots = slots
        self.active = np.zeros(slots, bool)
        self.rid = [-1] * slots
        self.pos = np.zeros(slots, np.int32)
        self.remaining = np.zeros(slots, np.int32)
        self.queue = deque()
        self.out = {}

    def submit(self, req):
        self.queue.append(req)

    def prefill_pending(self):
        time.sleep(ADMIT_S)
        for s in range(self.slots):
            if self.active[s] or not self.queue:
                continue
            r = self.queue.popleft()
            self.active[s], self.rid[s] = True, r.rid
            self.pos[s], self.remaining[s] = len(r.prompt), r.n_new - 1
            self.out[r.rid] = [0]
            if r.n_new == 1:
                self.active[s] = False

    def step(self):
        time.sleep(STEP_S)
        for s in np.flatnonzero(self.active):
            self.out[self.rid[s]].append(0)
            self.pos[s] += 1
            self.remaining[s] -= 1
            if self.remaining[s] <= 0:
                self.active[s] = False


MIX = {"mode": "open_loop", "server": {"slots": 4, "max_seq": 64},
       "arrivals": {"rate_rps": 10.0},
       "prompt_tokens": {"median": 8, "sigma": 0.5, "min": 4, "max": 16},
       "output_tokens": {"median": 6, "sigma": 0.5, "min": 2, "max": 12}}


def test_open_loop_times_from_the_schedule():
    reqs = traffic.requests(MIX, 100, 1.0, 11)
    win = serve.serve(FakeServer(4), reqs, MIX, 1.0, serve.spans(False))
    assert win.failed == 0 and len(win.counted) == len(reqs) == 10
    for rid in win.counted:
        lg = win.logs[rid]
        assert lg.done and lg.admit_start >= lg.due
        # the first token comes with the admission round that takes it
        assert lg.first - lg.due >= ADMIT_S
    ttft = readings.ttfts_ms(win)
    assert len(ttft) == len(win.counted)       # every request of the window
    assert ttft == [(win.logs[r].first - win.logs[r].due) * 1e3
                    for r in win.counted]
    assert readings.p90(ttft) == float(np.percentile(ttft, 90))


def test_backlog_counts_tokens_of_requests_in_flight():
    mix = {**MIX, "mode": "backlog", "requests": 40, "in_flight": True,
           "output_tokens": {"median": 60, "sigma": 0.3, "min": 40,
                             "max": 62},
           "server": {"slots": 4, "max_seq": 80}}
    reqs = traffic.requests(mix, 100, 0.5, 3)
    win = serve.serve(FakeServer(4), reqs, mix, 0.5, serve.spans(False))
    # the in-flight requests' first tokens came before the window opened
    delivered = sum(win.logs[r].n for r in win.counted) - 4
    finished = sum(win.logs[r].n for r in win.counted if win.logs[r].done)
    assert win.tokens == delivered > finished
    assert win.seconds >= 0.5
    assert win.failed == 0
    # steps kept are those of the window, each with its active rows
    assert sum(n for n, _ in win.steps) + sum(
        len(lens) for _, _, lens in win.admissions) == win.tokens


def test_tails_count_unfinished_requests():
    logs = {i: serve.Log(due=0.0, prompt_len=4, n_new=5, first=0.1,
                         last=0.5, n=5) for i in range(9)}
    logs[9] = serve.Log(due=0.0, prompt_len=4, n_new=5)       # never served
    win = serve.Window(seconds=1.0, tokens=45, logs=logs,
                       counted=list(range(10)), failed=1, steps=[],
                       admissions=[], slow=[], compiles=0, opened=0.0)
    ttft = readings.ttfts_ms(win)
    assert len(ttft) == 10 and math.isinf(ttft[-1])
    assert abs(readings.tpots_ms(win)[0] - 100.0) < 1e-9
    # one request in ten never finished: the 90th percentile reaches it
    assert readings.p90(ttft) is None
    assert readings.p90(ttft[:-1]) is not None
