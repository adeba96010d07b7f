"""Device time of the Pallas kernels per dispatched batch. Layer: score
kernels (``kernels/ops.py`` -> ``decompress_score.py``,
``fused_gather_score.py``)."""


def read(run):
    if run.trace is None or run.trace.pallas_s <= 0:
        return None
    return run.trace.per_batch_ms(run.trace.pallas_s)
