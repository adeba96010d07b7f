"""Device time per dispatched batch of the ops under the
``warp.gather_score`` named scope: the candidate gathers, decompression
and scoring, Pallas kernel included. Layer: gather-decompress-score
(``core/engine.py``, ``kernels/``)."""

from tpubench import scopes


def read(run):
    return scopes.stage_ms(run, "warp.gather_score")
