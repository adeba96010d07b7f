"""Device time per dispatched batch of the ops under the ``warp.select``
named scope: the centroid matmul, the top-k over the centroids and the
missing-similarity imputation. Layer: WARP_SELECT
(``core/warpselect.py``)."""

from tpubench import scopes


def read(run):
    return scopes.stage_ms(run, "warp.select")
