"""Mean wait of a dispatched request in the server's queue, from
``submit`` to the dispatch of its batch, on the server's clock: the
server's ``queue_wait_us`` counter (its scheduler's
``serving_queue_wait_seconds``) over the requests it dispatched. The
wait before ``submit``, while the loop's one thread runs a step, is not
in it. Layer: serving (``serving/batcher.py``,
``serving/scheduler.py``)."""


def read(run):
    c = run.counters
    dispatched = c.get("served", 0) - c.get("cache_hits", 0)
    if "queue_wait_us" not in c or dispatched <= 0:
        return None
    return c["queue_wait_us"] / dispatched / 1e3
