"""The trace reduction, on a hand-written trace whose numbers are known
and on a recorded trace of a few batches of ``lifestyle.offline`` on one
TPU v5e (``testdata/``)."""

from __future__ import annotations

import gzip

import pytest
from jax.profiler import ProfileData

from tpubench import spec, trace_reduce

MS = 1_000_000_000  # picoseconds


def _events(spec_):
    return "\n".join(
        f"    events {{ metadata_id: {m} offset_ps: {int(o * MS)} duration_ps: {int(d * MS)} }}"
        for m, o, d in spec_
    )


# Device: fusion 0-2 ms, kernel 1-3.5 ms (overlapping), fusion 6-7 ms.
# Host: bench.step 0-4 ms, bench.wait_arrival 4-5.5 ms, bench.step 5.5-8 ms.
HAND = f"""
planes {{
  id: 1
  name: "/device:TPU:0"
  lines {{
    id: 1
    name: "XLA Ops"
    timestamp_ns: 1000000
{_events([(1, 0, 2), (2, 1, 2.5), (1, 6, 1)])}
  }}
  lines {{
    id: 2
    name: "XLA Modules"
    timestamp_ns: 1000000
{_events([(3, 0, 8)])}
  }}
  event_metadata {{ key: 1 value {{ id: 1 name: "fusion.3" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "tpu_custom_call.1" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "jit_search" }} }}
}}
planes {{
  id: 2
  name: "/host:CPU"
  lines {{
    id: 7
    name: "python"
    timestamp_ns: 1000000
{_events([(1, 0, 4), (2, 4, 1.5), (1, 5.5, 2.5)])}
  }}
  event_metadata {{ key: 1 value {{ id: 1 name: "bench.step" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "bench.wait_arrival" }} }}
}}
"""


def test_reduction_of_a_hand_written_trace():
    s = trace_reduce.reduce(ProfileData.from_text_proto(HAND))
    assert s.window_s == pytest.approx(8e-3)
    assert s.busy_s == pytest.approx(4.5e-3)  # union of [0, 3.5] and [6, 7]
    assert s.pallas_s == pytest.approx(2.5e-3) and s.xla_s == pytest.approx(3e-3)
    assert [b for _, _, b in s.batches] == pytest.approx([3.5e-3, 1e-3])
    assert s.host_gap_ms() == pytest.approx(((4 - 3.5) + (2.5 - 1)) / 2)
    assert s.per_batch_ms(s.pallas_s) == pytest.approx(1.25)
    gaps = trace_reduce.breakdown(s)["idle_gaps"]
    assert gaps == [["bench.wait_arrival", pytest.approx(2.5e-3)], ["bench.step", pytest.approx(1e-3)]]
    ops = dict(trace_reduce.breakdown(s)["device_ops"])
    assert ops == {"fusion.3": pytest.approx(3e-3), "tpu_custom_call.1": pytest.approx(2.5e-3)}


def test_nothing_to_read_gives_nothing():
    assert trace_reduce.reduce(ProfileData.from_text_proto("planes { id: 1 name: \"/host:CPU\" }")) is None


def test_reduction_of_a_recorded_trace():
    files = sorted((spec.HERE / "testdata").glob("*.xplane.pb.gz"))
    assert files, "the recorded trace is missing"
    data = ProfileData.from_serialized_xspace(gzip.decompress(files[0].read_bytes()))
    s = trace_reduce.reduce(data)
    # As the run that recorded it reported them (one TPU v5 lite, 12 batches).
    assert s.n_chips == 1 and len(s.batches) == 12
    assert s.busy_s == pytest.approx(2.9190198949999973, rel=1e-9)
    assert s.window_s == pytest.approx(2.970099124, rel=1e-9)
    assert s.host_gap_ms() == pytest.approx(3.6219191666669546, rel=1e-9)
    assert s.per_batch_ms(s.xla_s) == pytest.approx(237.6536208333331, rel=1e-9)
    assert s.per_batch_ms(s.pallas_s) == pytest.approx(5.598037083333368, rel=1e-9)
    b = trace_reduce.breakdown(s)
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 10
    assert b["device_ops"][0][0].startswith("%sort.")
    assert any("tpu_custom_call" in name for name, _ in b["device_ops"])
