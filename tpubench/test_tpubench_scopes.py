"""The stage join (``scopes.py``) on hand-written HLO and trace op names,
and the readers of the per-stage device times and the server's counters
on synthetic runs."""

from __future__ import annotations

import pytest

from tpubench import scopes, spec, trace_reduce
from tpubench.run import RunView

SEL = 'metadata={op_name="jit(_search_many)/jit(_search_one)/warp.select/sort"}'
RED = 'metadata={op_name="jit(finish_from_probes)/vmap(warp.reduce)/scatter-add"}'

# Module A: a sort under warp.select; a fusion with no scope of its own
# whose fused gather is under warp.gather_score; a layout copy with no
# scope; a fusion under warp.reduce (spelled as under vmap); fusion.7
# under warp.reduce here and under warp.select in module B; fusion.66,
# whose body's only scope is on a constant the compiler shared; fusion.12,
# whose scatter under warp.reduce sits in a fusion nested in it.
MODULE_A = f"""HloModule jit__search_many, is_scheduled=true

%fused_computation.4 (param_0: u8[100,64], param_1: s32[10]) -> u8[10,64] {{
  %param_0 = u8[100,64]{{1,0}} parameter(0)
  %param_1 = s32[10]{{0}} parameter(1)
  ROOT %gather.1 = u8[10,64]{{1,0}} gather(%param_0, %param_1), offset_dims={{1}}, slice_sizes={{1,64}}, metadata={{op_name="jit(_search_many)/jit(_search_one)/warp.gather_score/gather"}}
}}

%fc.130 (param_0.363: s32[8,128]) -> s32[8,128] {{
  %param_0.363 = s32[8,128]{{1,0}} parameter(0)
  %constant.572 = s32[] constant(0), {SEL}
  ROOT %reduce-window.8 = s32[8,128]{{1,0}} reduce-window(%param_0.363, %constant.572), window={{size=1x128 pad=0_0x127_0}}, to_apply=%region_8.27
}}

%fused_computation.12.clone (param_0.204: f32[16], param_1.280: s32[16]) -> f32[16] {{
  %param_0.204 = f32[16]{{0}} parameter(0)
  %param_1.280 = s32[16]{{0}} parameter(1)
  ROOT %scatter.3 = f32[16]{{0}} scatter(%param_0.204, %param_1.280, %param_0.204), to_apply=%add, {RED}
}}

%fused_computation.73 (param_0.202: f32[16], param_1.279: s32[16]) -> f32[16] {{
  %param_0.202 = f32[16]{{0}} parameter(0)
  %param_1.279 = s32[16]{{0}} parameter(1)
  ROOT %fusion.40 = f32[16]{{0}} fusion(%param_0.202, %param_1.279), kind=kCustom, calls=%fused_computation.12.clone
}}

ENTRY %main.9 (q.1: f32[8,32,128], codes.1: u8[100,64]) -> f32[16] {{
  %q.1 = f32[8,32,128]{{2,1,0}} parameter(0), metadata={{op_name="q"}}
  %sort.22 = (f32[8,32,16]{{2,1,0}}, s32[8,32,16]{{2,1,0}}) sort(%fusion.63, %iota.17), dimensions={{2}}, is_stable=true, to_apply=%cmp, {SEL}
  %copy.22 = u8[100,64]{{1,0}} copy(%codes.1)
  %fusion.4 = u8[10,64]{{1,0}} fusion(%copy.22, %copy-done.12), kind=kCustom, calls=%fused_computation.4
  %fusion.7 = s32[16]{{0}} fusion(%sort.22), kind=kLoop, calls=%fc.7, {RED}
  %fusion.66 = s32[8,128]{{1,0}} fusion(%copy.25), kind=kOutput, calls=%fc.130
  %fusion.12 = f32[16]{{0}} fusion(%bitcast.110, %bitcast.106), kind=kCustom, calls=%fused_computation.73
  ROOT %fusion.10 = f32[16]{{0}} fusion(%fusion.4, %fusion.7), kind=kLoop, calls=%fc.10, {RED}
}}
"""
MODULE_B = f"""HloModule jit_select_probes, is_scheduled=true

ENTRY %main.2 (q.1: f32[8,32,128]) -> s32[16] {{
  %fusion.7 = s32[16]{{0}} fusion(%sort.22), kind=kLoop, calls=%fc.7, {SEL}
  %fusion.4 = u8[10,64]{{1,0}} fusion(%copy.22, %copy-done.12), kind=kCustom, calls=%fc.4, metadata={{op_name="jit(f)/warp.gather_score/gather"}}
}}
"""

# The trace names its ops by their instruction text, with operand shapes
# and the chip's tiled layouts, and without metadata.
TRACE_OPS = {
    "%sort.22 = (f32[8,32,16]{2,1,0:T(8,128)}, s32[8,32,16]{2,1,0:T(8,128)}) sort("
    "f32[8,32,16]{2,1,0:T(8,128)} %fusion.63, s32[8,32,16]{2,1,0:T(8,128)} %iota.17), "
    "dimensions={2}, is_stable=true, to_apply=%cmp": 0.030,
    "%fusion.4 = u8[10,64]{1,0:T(8,128)(4,1)} fusion(u8[100,64]{1,0:T(8,128)(4,1)} %copy.22, "
    "s32[10]{0:T(1024)} %copy-done.12), kind=kCustom, calls=%fused_computation.4": 0.020,
    "%fusion.10 = f32[16]{0:T(1024)} fusion(u8[10,64]{1,0} %fusion.4, s32[16]{0} %fusion.7), "
    "kind=kLoop, calls=%fc.10": 0.010,
    "%copy.22 = u8[100,64]{1,0:T(8,128)(4,1)} copy(u8[100,64]{0,1:T(8,128)(4,1)} %codes.1)": 0.004,
    "%fusion.7 = s32[16]{0:T(1024)} fusion(f32[8,32,16]{2,1,0} %sort.22), kind=kLoop, calls=%fc.7": 0.002,
    "%fusion.99 = f32[4]{0} fusion(f32[4]{0} %x), kind=kLoop, calls=%fc.99": 0.001,
    "%fusion.66 = s32[8,128]{1,0:T(8,128)} fusion(s32[8,128]{1,0:T(8,128)} %copy.25), kind=kOutput, "
    "calls=%fc.130": 0.002,
}


def test_scopes_map_hand_written_hlo_to_stages():
    stages = scopes.stage_map([MODULE_A, MODULE_B])
    key = scopes.op_key
    assert stages[key("%sort.22 = (f32[8,32,16]{2,1,0}, s32[8,32,16]{2,1,0}) sort(%fusion.63, %iota.17)")] == "warp.select"
    # A fusion takes the one scope of what it fuses; both modules agree.
    assert stages[key("%fusion.4 = u8[10,64]{1,0} fusion(%copy.22, %copy-done.12), kind=kCustom")] == "warp.gather_score"
    # Through a fusion nested in the fusion.
    assert stages[key("%fusion.12 = f32[16]{0} fusion(%bitcast.110, %bitcast.106)")] == "warp.reduce"
    # Two modules disagree on one key: no stage.
    assert stages[key("%fusion.7 = s32[16]{0} fusion(%sort.22)")] is None
    assert key("%copy.22 = u8[100,64]{1,0} copy(%codes.1)") not in stages
    per_stage, rest = scopes.split(TRACE_OPS, [MODULE_A, MODULE_B])
    assert per_stage == pytest.approx(
        {"warp.select": 0.030, "warp.gather_score": 0.020, "warp.reduce": 0.010}
    )
    # Unattributed: no scope (copy.22; fusion.66 but for a shared
    # constant), ambiguous (fusion.7), in no module (fusion.99).
    assert sorted(n.split(" =")[0] for n in rest) == [
        "%copy.22", "%fusion.66", "%fusion.7", "%fusion.99"
    ]
    assert sum(rest.values()) == pytest.approx(0.009)


def test_op_key_ignores_layouts_operand_shapes_and_attributes():
    bare = "  ROOT %fusion.32 = (f32[8,100]{1,0}, s32[8,100]{1,0}) fusion(%reshape.182, %get-tuple-element.30), kind=kCustom, calls=%fc.57, metadata={op_name=\"a\"}"
    traced = (
        "%fusion.32 = (f32[8,100]{1,0:T(8,128)S(1)}, s32[8,100]{1,0:T(8,128)S(1)}) fusion("
        "f32[8,373760]{1,0:T(8,128)} %reshape.182, s32[8,373759]{1,0:T(8,128)} %get-tuple-element.30), "
        "kind=kCustom, calls=%fused_computation.57"
    )
    assert scopes.op_key(bare) == scopes.op_key(traced) == (
        "fusion.32 = (f32[8,100], s32[8,100]) fusion(reshape.182,get-tuple-element.30)"
    )
    assert scopes.op_key("HloModule jit_f, is_scheduled=true") is None


def _trace(op_seconds, n_batches=2):
    return trace_reduce.TraceSummary(
        window=(0.0, 1.0), busy_s=0.07, pallas_s=0.0, xla_s=0.07, op_seconds=op_seconds,
        step_busy=[(0.0, 0.5, 0.03)] * n_batches, gaps=[], n_chips=1,
    )


def _read(name, run):
    return spec.load_reader(spec.reader_path(spec.HERE, name))(run)


STAGE_METRICS = {
    "warp_select_ms.offline": 15.0, "gather_score_ms.offline": 10.0, "reduce_ms.offline": 5.0,
}


def test_stage_readers_on_a_synthetic_run(monkeypatch):
    monkeypatch.setattr(scopes, "live_hlo_texts", lambda: [MODULE_A, MODULE_B])
    run = RunView(cell=None, window=None, counters={}, trace=_trace(TRACE_OPS), work={})
    for name, ms in STAGE_METRICS.items():
        assert _read(name, run) == pytest.approx(ms)
    # The three stages and the unattributed rest add up to all device time.
    assert sum(STAGE_METRICS.values()) + 4.5 == pytest.approx(
        run.trace.per_batch_ms(sum(TRACE_OPS.values()))
    )


def test_stage_readers_give_nothing_without_trace_or_scopes(monkeypatch):
    monkeypatch.setattr(scopes, "live_hlo_texts", lambda: [MODULE_A.replace("warp.", "")])
    no_trace = RunView(cell=None, window=None, counters={}, trace=None, work={})
    unscoped = RunView(cell=None, window=None, counters={}, trace=_trace(TRACE_OPS), work={})
    for name in STAGE_METRICS:
        assert _read(name, no_trace) is None
        assert _read(name, unscoped) is None


def test_counter_readers():
    counters = {"served": 10, "cache_hits": 2, "batches": 4, "queue_wait_us": 80_000, "step_host_us": 12_000}
    run = RunView(cell=None, window=None, counters=counters, trace=None, work={})
    assert _read("server_queue_ms.steady", run) == pytest.approx(10.0)  # 80 ms over 8 dispatched
    assert _read("step_host_ms.offline", run) == pytest.approx(3.0)  # 12 ms over 4 batches
    # A server without the counters (or with nothing dispatched): nothing.
    old = RunView(cell=None, window=None, counters={"served": 10, "cache_hits": 0, "batches": 4}, trace=None, work={})
    idle = RunView(cell=None, window=None, counters=dict(counters, served=2, batches=0), trace=None, work={})
    for name in ("server_queue_ms.steady", "step_host_ms.offline"):
        assert _read(name, old) is None
        assert _read(name, idle) is None
