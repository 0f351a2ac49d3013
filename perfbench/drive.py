"""Client side of a run: sends a cell's requests into the serving loop's
in-process API and records when each token reaches the client.

``EngineLoop.submit`` is what the HTTP handler calls; each ``Stream``'s
event queue is what its SSE pump reads.  A request's clock starts when it
is due (open loop) or sent (closed loop), and every token is stamped on
the host clock when the client takes it off the queue.  An open loop waits
for every answer due in its window; a closed loop cancels what still runs
at the close.
"""

from __future__ import annotations

import dataclasses
import itertools
import threading
import time
from typing import Optional

#: seconds past the window's close that an open loop waits for answers
DRAIN_S = 90.0


@dataclasses.dataclass
class Record:
    req: object                     # traffic.Request
    due: Optional[float] = None     # when it was due (open loop)
    sent: Optional[float] = None
    stream: object = None           # serving.Stream once submitted
    error: Optional[str] = None
    tokens: list = dataclasses.field(default_factory=list)
    times: list = dataclasses.field(default_factory=list)
    end: Optional[str] = None       # "done" | "cancelled"
    closed_at: Optional[float] = None

    @property
    def complete(self) -> bool:
        return self.end == "done" and len(self.tokens) == self.req.max_new


def _submit(loop, rec: Record, temperature: float) -> bool:
    r = rec.req
    rec.sent = time.monotonic()
    try:
        rec.stream = loop.submit(
            r.prompt, max_new_tokens=r.max_new,
            temperature=0.0 if r.greedy else temperature, seed=r.seed)
    except Exception as e:      # refused: counts as failed, run goes on
        rec.error = repr(e)
        rec.closed_at = rec.sent
        return False
    return True


def _read(rec: Record):
    while True:
        kind, payload = rec.stream.events.get()
        now = time.monotonic()
        if kind == "token":
            rec.times.append(now)
            rec.tokens.append(int(payload["token"]))
        else:
            rec.end, rec.closed_at = kind, now
            return


def open_loop(loop, reqs, t0: float, seconds: float, temperature: float):
    """Send each request when it is due; returns once every answer came
    or ``DRAIN_S`` after the window closed (then cancels the rest)."""
    recs, readers = [], []
    for r in reqs:
        due = t0 + r.arrival
        delay = due - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        rec = Record(req=r, due=due)
        recs.append(rec)
        if _submit(loop, rec, temperature):
            th = threading.Thread(target=_read, args=(rec,), daemon=True)
            th.start()
            readers.append(th)
    time.sleep(max(0.0, t0 + seconds - time.monotonic()))
    deadline = t0 + seconds + DRAIN_S
    for th in readers:
        th.join(max(0.0, deadline - time.monotonic()))
    _cancel_open(loop, recs)
    for th in readers:
        th.join(10.0)
    return recs


def closed_loop(loop, reqs, clients: int, t0: float, seconds: float,
                temperature: float):
    """``clients`` clients; client ``c`` sends requests ``c, c + clients,
    ...`` of ``reqs`` (wrapping), each when its last one finished, until
    the window closes.  Requests still running then are cancelled: only
    answers finished inside the window are compared."""
    import copy

    t_end = t0 + seconds
    recs, lock = [], threading.Lock()

    def client(c):
        for j in itertools.count():
            # sent under the lock, so the close below sees every stream
            with lock:
                if time.monotonic() >= t_end:
                    return
                k = c + clients * j
                req = copy.copy(reqs[k % len(reqs)])
                req.index = k
                rec = Record(req=req)
                recs.append(rec)
                sent = _submit(loop, rec, temperature)
            if sent:
                _read(rec)

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(clients)]
    for th in threads:
        th.start()
    time.sleep(max(0.0, t_end - time.monotonic()))
    with lock:
        _cancel_open(loop, recs)
    for th in threads:
        th.join(10.0)
    return recs


def _cancel_open(loop, recs):
    for rec in list(recs):
        if rec.stream is not None and rec.end is None:
            loop.cancel(rec.stream.rid)
