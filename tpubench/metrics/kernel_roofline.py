"""The score kernels' share of their roofline, in percent: the least time
the chip needs for the stage-2 work of every request the traced window
served (its real candidate tokens: codes and a 4-byte doc id read, and
2 * dim operations, each; ``tpubench/roofline.py``) at the peaks of the
chip's row of ``roofline.PEAKS``, over the Pallas kernels' device time.
Layer: score kernels."""

from tpubench import roofline


def read(run):
    tr, work = run.trace, run.work
    if tr is None or tr.pallas_s <= 0 or not work.get("candidate_tokens") or work.get("peaks") is None:
        return None
    c, n = run.cell.config, work["candidate_tokens"]
    t, _ = roofline.least_time(
        roofline.candidate_bytes(n, c["dim"], c["nbits"]), roofline.candidate_ops(n, c["dim"]), work["peaks"]
    )
    return t / tr.pallas_s * 100.0
