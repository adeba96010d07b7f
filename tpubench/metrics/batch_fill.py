"""Share of batch slots that carried a request over the window:
``served / (batches * max_batch)`` from the server's own counters.
Layer: serving (``serving/batcher.py``, ``serving/scheduler.py``)."""


def read(run):
    batches = run.counters.get("batches", 0)
    if not batches:
        return None
    return run.counters["served"] / (batches * run.cell.config["serving"]["max_batch"])
