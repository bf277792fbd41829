"""The continuous server driven as a function front end drives it, and what
it did written down.

The window calls the server's online entries: ``submit`` when a request falls
due, ``prefill_pending`` to admit queued requests into free slots, ``step``
for one decode step of every active slot.  Each returns only once its tokens
are on the host (the first tokens' argmax, the step's token block), so a
token counts as delivered when the call that made it returns, and the host
spans around the calls bound the device work inside them.

Bookkeeping inside the window is a few integers per call: no token lists, no
logits.  The server keeps the tokens it served (``srv.out``); the check reads
them after the window has closed.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import heapq
import math
import time
from collections import deque

import numpy as np

from bench.lib import traffic

DRAIN_LIMIT_S = 60.0   # open loop: how long requests due in the window may
                       # take to finish after it closes before they fail
SLOW_PASSES = 3


@dataclasses.dataclass
class Log:
    due: float | None
    prompt_len: int
    n_new: int
    admit_start: float = math.nan
    first: float = math.nan
    last: float = math.nan
    n: int = 0
    slot: int = -1                 # the slot it was admitted to

    @property
    def done(self) -> bool:
        return self.n >= self.n_new


@dataclasses.dataclass
class Window:
    """What one window did, on the host clock, in seconds after it opened."""
    seconds: float                 # open to close
    tokens: int                    # output tokens delivered in the window
    logs: dict                     # rid -> Log, every request
    counted: list                  # rids the end-to-end metrics are over
    failed: int                    # counted requests never finished
    steps: list                    # per decode step: (active, attended)
    admissions: list               # per admission: (start, seconds, [prompt lens])
    slow: list                     # slowest passes: (seconds, start, admit, step)
    compiles: int                  # backend compilations inside the window
    opened: float                  # perf_counter when the window opened
    lateness_s: float = 0.0        # open loop: most a submit ran late


class CompileCounter:
    """Counts the backend compilations JAX reports while armed."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.armed, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, event, duration, **_):
        if self.armed and event == self.EVENT:
            self.count += 1


def spans(enabled: bool):
    """``span(name)``: a profiler annotation when tracing, else nothing."""
    if not enabled:
        return lambda name: contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation


def make_server(pcfg, params, mix: dict):
    """The program's ``ContinuousServer`` serving ``params``.  The server
    builds its own weights from a seed baked into a jitted program, which
    would compile anew for every seed; the benchmark's weights, made from
    the key as an argument, are handed in instead."""
    from repro.models import api
    from repro.serving.continuous import ContinuousServer
    own = api.build_params
    api.build_params = lambda cfg, seed=0, shardings=None: params
    try:
        srv = ContinuousServer(pcfg, slots=mix["server"]["slots"],
                               max_seq=mix["server"]["max_seq"])
    finally:
        api.build_params = own
    return srv


def _request(rid, prompt, n_new):
    from repro.serving.continuous import Request
    return Request(rid=rid, prompt=[int(t) for t in prompt], n_new=int(n_new))


def warm_up(srv, mix: dict, vocab: int) -> None:
    """Run every shape the window can meet: each prefill bucket the mix's
    prompts reach, each admission width from one row to every slot (the
    scatter and the slicing of the prefilled rows compile per width), and
    the decode step.  Warm-up ids are negative, and the cache rows it leaves
    are overwritten before any request reads them."""
    rng = np.random.default_rng(0)
    rid = -1

    def admit(lengths, n_new=1):
        nonlocal rid
        for n in lengths:
            srv.submit(_request(rid, rng.integers(0, vocab, n), n_new))
            rid -= 1
        srv.prefill_pending()

    cap = mix["server"]["max_seq"] - 1
    buckets = traffic.prefill_buckets(mix)
    for b in buckets:
        admit([min(b, cap)])
    for m in range(2, srv.slots + 1):
        admit([min(buckets[0], cap)] * m)
    admit([min(buckets[0], cap)], n_new=2)
    srv.step()
    assert not srv.active.any() and not srv.queue
    for r in [r for r in srv.out if r < 0]:
        del srv.out[r]


def _next_admitted(srv) -> list:
    """The ids that ``prefill_pending`` will admit: the queue's head, as
    many as there are free slots."""
    free = int(srv.slots - srv.active.sum())
    return [r.rid for r in list(srv.queue)[:free]]


def serve(srv, reqs: list, mix: dict, seconds: float, span, profiler=None,
          counter: CompileCounter | None = None) -> Window:
    """Drive one window.  ``backlog``: every request is queued, and the
    first admission round made, before the window opens; the window ends
    at the first pass boundary after ``seconds``.  ``open_loop``: each
    request is submitted when it falls due; after the window closes the loop
    serves on, with no new arrivals, until every request due in it has
    finished or ``DRAIN_LIMIT_S`` has passed.  Only the window's steps,
    admissions and slowest passes are kept; the profiler, when given, runs
    on through the drain and its reduction keeps the window alone."""
    logs = {r.rid: Log(r.due, len(r.prompt), r.n_new) for r in reqs}
    backlog = mix["mode"] == "backlog"
    pending = deque() if backlog else deque(
        sorted((r for r in reqs if r.due < seconds), key=lambda r: r.due))
    counted = [r.rid for r in pending]
    steps, admissions, slow = [], [], []
    clock = time.perf_counter
    t0 = 0.0
    tokens = 0
    lateness = 0.0

    def admit():
        nonlocal tokens
        rids = _next_admitted(srv)
        start = clock() - t0
        with span("bench.admit"):
            srv.prefill_pending()
        end = clock() - t0
        where = {r: s for s, r in enumerate(srv.rid)}
        for rid in rids:
            lg = logs[rid]
            lg.admit_start, lg.first, lg.last, lg.n = start, end, end, 1
            lg.slot = where.get(rid, -1)
        tokens += len(rids)
        admissions.append((start, end - start,
                           [logs[r].prompt_len for r in rids]))
        return rids, end - start

    def step():
        nonlocal tokens
        act = np.flatnonzero(srv.active)
        rids = [srv.rid[s] for s in act]
        attended = int(srv.pos[act].sum()) + len(act)
        start = clock()
        with span("bench.step"):
            srv.step()
        end = clock()
        for rid in rids:
            lg = logs[rid]
            lg.n += 1
            lg.last = end - t0
        tokens += len(rids)
        steps.append((len(rids), attended))
        return end - start

    if backlog:
        for r in reqs:
            srv.submit(_request(r.rid, r.prompt, r.n_new))
        counted, _ = admit()
        steps.clear()
        admissions.clear()
        tokens = 0
        for rid in counted:      # already in flight when the window opens
            logs[rid].admit_start = logs[rid].first = logs[rid].last = 0.0

    gc.collect()
    gc.disable()
    if counter:
        counter.armed = True
    if profiler:
        profiler.start()
    closed = None
    window_span = contextlib.ExitStack()
    window_span.enter_context(span("bench.window"))
    t0 = clock()
    try:
        while True:
            now = clock() - t0
            if closed is None and now >= seconds:
                closed = (now, tokens, len(steps), len(admissions))
                window_span.close()
                if backlog:
                    break
            if closed is not None and (
                    all(logs[r].done for r in counted)
                    or now >= seconds + DRAIN_LIMIT_S):
                break
            while pending and pending[0].due <= now:
                r = pending.popleft()
                lateness = max(lateness, now - r.due)
                srv.submit(_request(r.rid, r.prompt, r.n_new))
            a = s = 0.0
            if srv.queue and not srv.active.all():
                rids, a = admit()
                if backlog and closed is None:
                    counted.extend(rids)
            if srv.active.any():
                s = step()
            elif pending and not srv.queue:
                with span("bench.wait"):
                    time.sleep(max(0.0, min(pending[0].due, seconds)
                                   - (clock() - t0)))
                continue
            elif not srv.queue:
                if closed is not None:
                    break    # nothing running, queued or still to come
                with span("bench.wait"):
                    time.sleep(max(0.0, seconds - (clock() - t0)))
                continue
            if closed is not None:
                continue
            dur = clock() - t0 - now
            item = (dur, now, a, s)
            if len(slow) < SLOW_PASSES:
                heapq.heappush(slow, item)
            elif dur > slow[0][0]:
                heapq.heapreplace(slow, item)
    finally:
        window_span.close()
        if profiler:
            profiler.stop()
        if counter:
            counter.armed = False
        gc.enable()
    if closed is None:
        closed = (clock() - t0, tokens, len(steps), len(admissions))
    end, tokens_in, n_steps, n_admissions = closed
    return Window(
        seconds=end, tokens=tokens_in, logs=logs, counted=counted,
        failed=0 if backlog else sum(1 for r in counted
                                     if not logs[r].done),
        steps=steps[:n_steps], admissions=admissions[:n_admissions],
        slow=sorted(slow, reverse=True),
        compiles=counter.count if counter else 0, opened=t0,
        lateness_s=lateness)
