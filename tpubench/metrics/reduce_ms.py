"""Device time per dispatched batch of the ops under the ``warp.reduce``
named scope: the two-stage reduction to top-k. Layer: two-stage
reduction (``core/reduction.py``)."""

from tpubench import scopes


def read(run):
    return scopes.stage_ms(run, "warp.reduce")
