"""Device time of the operations that are not Pallas kernels (WARP_SELECT,
the gathers, the two-stage reduction, glue), per dispatched batch.
Layer: engine XLA stages (``core/warpselect.py``, ``core/reduction.py``,
``core/engine.py``)."""


def read(run):
    return None if run.trace is None else run.trace.per_batch_ms(run.trace.xla_s)
