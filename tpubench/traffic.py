"""Traffic: the arrival schedule of a mix, and the two loops that drive
a ``RetrievalServer`` with it on the host's wall clock.

A mix is a data file (``traffic/<name>.json``):

- ``{"loop": "open", "load_of_knee": f, ...}``: Poisson arrivals at
  ``f`` times the configuration's ``knee_qps``. Latency runs from each
  request's due time to the end of the ``step`` that served it, so a
  stall of the loop delays every request due behind it.
- ``{"loop": "closed", "outstanding": n, ...}``: ``n`` requests in
  flight at all times; each reply is answered at once by a new request.

Both use ``time.monotonic``, the clock ``RetrievalServer`` schedules its
batch deadlines on, and run in the process's one thread, as a server
loop does.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import time

import numpy as np

CLOCK = time.monotonic
# How long past the window's close the loops wait for its last replies.
GRACE_S = 60.0


def poisson_gaps(rate: float, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` inter-arrival gaps of a Poisson process at ``rate`` per
    second: the exponential distribution's quantiles at ``(i + 0.5) / n``,
    in an order drawn from ``rng``."""
    u = (np.arange(n, dtype=np.float64) + 0.5) / n
    return rng.permutation(-np.log1p(-u) / rate)


def offered_rate(config: dict, traffic: dict) -> float:
    return float(traffic["load_of_knee"]) * float(config["knee_qps"])


def arrival_gaps(config: dict, traffic: dict, n: int) -> np.ndarray:
    """An open mix's ``n`` gaps, in the order its ``order_seed`` draws.
    The order is the mix's, not the run's: in a queue at 0.8 of capacity
    the order of the same gaps moves the tail by a quarter from one order
    to the next, far more than two runs of one order differ, so every run
    of a cell offers the same arrivals and the run's seed draws the index
    and the queries."""
    rng = np.random.default_rng([3, int(traffic["order_seed"])])
    return poisson_gaps(offered_rate(config, traffic), n, rng)


def pool_size(config: dict, traffic: dict, seconds: float) -> int:
    """Distinct queries a run draws. An open loop never repeats one. A
    closed loop cycles through its pool, which is sized for three times
    the knee and always well above the result cache, so a repeat is never
    served from the cache."""
    if traffic["loop"] == "open":
        return math.ceil(offered_rate(config, traffic) * (seconds + traffic["tail_s"]))
    floor = 4 * (config["serving"]["cache_size"] + traffic["outstanding"])
    return max(floor, math.ceil(3 * config["knee_qps"] * (seconds + traffic["tail_s"])))


@dataclasses.dataclass
class Window:
    """What one measured window did, on ``CLOCK``. Requests are numbered
    in the order they were sent; ``pool_idx[j]`` is the query request
    ``j`` sent. ``counted`` marks the requests the window answers for:
    an open loop's requests due before the close, every request a closed
    loop sent."""

    t0: float
    t1: float
    pool_idx: np.ndarray
    due: np.ndarray  # when it was due (closed loop: when it was sent)
    sent: np.ndarray
    started: np.ndarray  # start of the step that served it
    done: np.ndarray  # end of that step; NaN if never served
    counted: np.ndarray
    replies: dict  # request number -> (scores, doc_ids)
    steps: list  # (start, end, request numbers served)

    @property
    def n_counted(self) -> int:
        return int(self.counted.sum())

    @property
    def n_failed(self) -> int:
        return int((self.counted & np.isnan(self.done)).sum())


def _annotator(trace: bool):
    if not trace:
        return lambda name: contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation


class _Loop:
    def __init__(self, server, qs, ms, trace: bool):
        from repro.serving import PENDING

        self.pending = PENDING
        self.server, self.qs, self.ms = server, qs, ms
        self.annotate = _annotator(trace)
        self.rows: list = []  # [pool_idx, due, sent, started, done]
        self.replies: dict = {}
        self.steps: list = []
        self.outstanding: dict = {}

    @property
    def n(self) -> int:
        return len(self.rows)

    def submit(self, pool_i: int, due: float) -> None:
        with self.annotate("bench.submit"):
            rid = self.server.submit(self.qs[pool_i], self.ms[pool_i])
        self.outstanding[rid] = self.n
        self.rows.append([pool_i, due, CLOCK(), np.nan, np.nan])

    def step(self) -> int:
        s0 = CLOCK()
        with self.annotate("bench.step"):
            served = self.server.step()
        if not served:
            return 0
        s1 = CLOCK()
        got = []
        for rid, j in list(self.outstanding.items()):
            out = self.server.poll(rid)
            if out is not self.pending:
                del self.outstanding[rid]
                self.rows[j][3:] = [s0, s1]
                self.replies[j] = out
                got.append(j)
        self.steps.append((s0, s1, got))
        return served

    def window(self, t0: float, t1: float, counted: np.ndarray) -> Window:
        a = np.asarray(self.rows, np.float64).reshape(-1, 5)
        return Window(
            t0=t0, t1=t1, pool_idx=a[:, 0].astype(np.int64), due=a[:, 1],
            sent=a[:, 2], started=a[:, 3], done=a[:, 4], counted=counted,
            replies=self.replies, steps=self.steps,
        )


def drive_open(server, qs, ms, gaps: np.ndarray, seconds: float, *, trace: bool = False) -> Window:
    """Send request ``j`` at its due time ``t0 + sum(gaps[:j])`` and run
    the server loop until every request due in ``[t0, t0 + seconds)``
    has its reply (or ``GRACE_S`` has passed since the close). Arrivals
    keep coming after the close, so the window's last requests queue as
    the others did."""
    n = len(gaps)
    loop = _Loop(server, qs, ms, trace)
    t0 = CLOCK()
    due = t0 + np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    t1 = t0 + seconds
    n_window = int((due < t1).sum())
    while True:
        now = CLOCK()
        while loop.n < n and due[loop.n] <= now:
            loop.submit(loop.n, due[loop.n])
        if now > t1 + GRACE_S or (
            loop.n >= n_window and min(loop.outstanding.values(), default=n) >= n_window
        ):
            break
        if loop.step():
            continue
        wake = due[loop.n] if loop.n < n else math.inf
        nd = server.next_deadline()
        if nd is not None:
            wake = min(wake, nd)
        if wake == math.inf:
            break
        with loop.annotate("bench.wait_arrival"):
            time.sleep(max(0.0, wake - CLOCK()))
    return loop.window(t0, t1, np.arange(loop.n) < n_window)


def drive_closed(server, qs, ms, outstanding: int, seconds: float, *, trace: bool = False) -> Window:
    """Keep ``outstanding`` requests in flight for ``seconds``, then stop
    sending and wait for the replies still due. The pool is cycled."""
    n_pool = len(qs)
    loop = _Loop(server, qs, ms, trace)
    t0 = CLOCK()
    t1 = t0 + seconds
    for _ in range(outstanding):
        loop.submit(loop.n % n_pool, CLOCK())
    while loop.outstanding and CLOCK() < t1 + GRACE_S:
        served = loop.step()
        if not served:
            with loop.annotate("bench.wait_arrival"):
                nd = server.next_deadline()
                time.sleep(max(0.0, (nd or CLOCK()) - CLOCK()))
        elif CLOCK() < t1:
            for _ in range(served):
                loop.submit(loop.n % n_pool, CLOCK())
    return loop.window(t0, t1, np.ones(loop.n, bool))
