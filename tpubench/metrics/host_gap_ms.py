"""Device idle time inside each ``step`` that dispatched a batch: the
step's length on the trace clock less the device time within it, mean
per batch. Layer: host dispatch (``RetrievalServer.step``,
``SearchPlan.retrieve_batch``)."""


def read(run):
    return None if run.trace is None else run.trace.host_gap_ms()
