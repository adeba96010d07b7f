"""Mean time a request waits before service: from its due time (when it
arrived) to the start of the ``step`` that served it, on the benchmark's
host clock. It holds the wait for the batch in service when the request
arrived, during which the one-threaded loop cannot submit it, and the
wait in the server's queue after it. Layer: serving
(``serving/batcher.py``, ``serving/scheduler.py``)."""

import numpy as np


def read(run):
    w = run.window
    m = w.counted & ~np.isnan(w.started)
    if not m.any():
        return None
    return float(np.mean(w.started[m] - w.due[m]) * 1e3)
