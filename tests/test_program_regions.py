"""The region vocabulary of the device programs and the map a compiled
program gives of itself (``flink_tpu/metrics/device.py``: ``classify_hlo``,
``program_regions``): from the HLO instruction a device trace names an
operation by to the region its time is booked under. The cases are cut
by hand from programs compiled for a v5e (``tests/test_tpu_lowering.py``
holds the whole programs to the same map)."""

import pathlib
import re

import pytest

from flink_tpu.metrics.device import PATH_SCOPES, PROGRAM_AUDIT, \
    REGION_SCOPES, UNNAMED, classify_hlo, clear_program_audit, \
    program_regions

_STEP = "jit(step)/shard_map/while/body"

#: one module, cut down: an entry that splits an int64 plane, loops over
#: exchange rounds and joins the plane again
_HLO = f"""HloModule jit_step, is_scheduled=true, entry_computation_layout={{(s64[8]{{0}})->s64[8]{{0}}}}

%region_add (a: u32[], b: u32[]) -> u32[] {{
  %a = u32[]{{:T(128)}} parameter(0)
  %b = u32[]{{:T(128)}} parameter(1)
  ROOT %add.1 = u32[]{{:T(128)}} add(%a, %b)
}}

%fused_sum (p0: u32[8], p1: s32[4], p2: u32[4]) -> u32[8] {{
  %p0 = u32[8]{{0:T(1024)}} parameter(0)
  %p1 = s32[4]{{0:T(1024)}} parameter(1)
  %p2 = u32[4]{{0:T(1024)}} parameter(2)
  ROOT %scatter-add.7 = u32[8]{{0:T(1024)}} scatter(%p0, %p1, %p2), to_apply=%region_add, metadata={{op_name="{_STEP}/mesh.fold/while/body/closed_call/fold.row/cond/branch_1_fun/while/body/cond/branch_1_fun/fold.scatter/fold.sum/scatter-add" stack_frame_id=9}}
}}

%fused_pathless (p0.1: u32[8], p1.1: s32[4], p2.1: u32[4]) -> u32[8] {{
  %p0.1 = u32[8]{{0:T(1024)}} parameter(0)
  %p1.1 = s32[4]{{0:T(1024)}} parameter(1)
  %p2.1 = u32[4]{{0:T(1024)}} parameter(2)
  %reshape.3 = u32[4]{{0:T(1024)}} reshape(%p2.1), metadata={{op_name="jit(step)/shard_map"}}
  ROOT %scatter.9 = u32[8]{{0:T(1024)}} scatter(%p0.1, %p1.1, %reshape.3), to_apply=%region_add
}}

%body (arg: (u32[], u32[8], u32[8], s32[4], u32[4])) -> (u32[], u32[8], u32[8], s32[4], u32[4]) {{
  %arg = (u32[]{{:T(128)}}, u32[8]{{0:T(1024)}}, u32[8]{{0:T(1024)}}, s32[4]{{0:T(1024)}}, u32[4]{{0:T(1024)}}) parameter(0)
  %gte.0 = u32[]{{:T(128)}} get-tuple-element(%arg), index=0
  %gte.1 = u32[8]{{0:T(1024)}} get-tuple-element(%arg), index=1
  %gte.3 = s32[4]{{0:T(1024)}} get-tuple-element(%arg), index=3
  %gte.4 = u32[4]{{0:T(1024)}} get-tuple-element(%arg), index=4
  %fusion.7 = u32[8]{{0:T(1024)S(1)}} fusion(%gte.1, %gte.3, %gte.4), kind=kCustom, calls=%fused_pathless, backend_config={{"flag_configs":[]}}
  %copy-start = (u32[8]{{0:T(1024)S(1)}}, u32[8]{{0:T(1024)}}, u32[]{{:S(2)}}) copy-start(%fusion.7)
  %copy-done = u32[8]{{0:T(1024)S(1)}} copy-done(%copy-start)
  %all-to-all.1 = u32[8]{{0:T(1024)}} all-to-all(%copy-done), replica_groups={{{{0,1,2,3}}}}, dimensions={{0}}, metadata={{op_name="{_STEP}/mesh.exchange/all_to_all" stack_frame_id=4}}
  %scatter.two = u32[8]{{0:T(1024)}} scatter(%gte.1, %gte.3, %gte.4), to_apply=%region_add
  %all-to-all.2 = u32[8]{{0:T(1024)}} all-to-all(%scatter.two), replica_groups={{{{0,1,2,3}}}}, dimensions={{0}}, metadata={{op_name="{_STEP}/mesh.exchange/all_to_all" stack_frame_id=4}}
  %sort.5 = u32[8]{{0:T(1024)}} sort(%scatter.two), dimensions={{0}}, to_apply=%region_add, metadata={{op_name="{_STEP}/mesh.probe/jit(lookup_or_insert)/probe.tail/cond/branch_0_fun/probe.compact/sort" stack_frame_id=5}}
  %fusion.8 = u32[8]{{0:T(1024)}} fusion(%all-to-all.1, %gte.3, %gte.4), kind=kCustom, calls=%fused_sum
  %gather.2 = u32[8]{{0:T(1024)}} gather(%fusion.8, %gte.3), offset_dims={{}}, metadata={{op_name="{_STEP}/mesh.probe/jit(lookup_or_insert)/probe.window0/probe.gather/gather" stack_frame_id=6}}
  %select.3 = u32[8]{{0:T(1024)}} select(%gather.2, %gather.2, %sort.5), metadata={{op_name="{_STEP}/mesh.exchange/exchange.pack/select_n" stack_frame_id=7}}
  %reshape.8 = u32[8]{{0:T(1024)}} reshape(%all-to-all.2), metadata={{op_name="{_STEP}/mesh.exchange/reshape" stack_frame_id=7}}
  %pmax = u32[]{{:T(128)}} all-reduce(%gte.0), to_apply=%region_add, metadata={{op_name="jit(step)/shard_map/mesh.sync/pmax"}}
  %add.9 = u32[]{{:T(128)}} add(%pmax, %gte.0), metadata={{op_name="{_STEP}/add"}}
  ROOT %tuple.1 = (u32[]{{:T(128)}}, u32[8]{{0:T(1024)}}, u32[8]{{0:T(1024)}}, s32[4]{{0:T(1024)}}, u32[4]{{0:T(1024)}}) tuple(%add.9, %select.3, %reshape.8, %gte.3, %gte.4)
}}

%cond (arg.1: (u32[], u32[8], u32[8], s32[4], u32[4])) -> pred[] {{
  %arg.1 = (u32[]{{:T(128)}}, u32[8]{{0:T(1024)}}, u32[8]{{0:T(1024)}}, s32[4]{{0:T(1024)}}, u32[4]{{0:T(1024)}}) parameter(0)
  %gte.9 = u32[]{{:T(128)}} get-tuple-element(%arg.1), index=0
  %constant.1 = u32[]{{:T(128)}} constant(3)
  ROOT %lt.1 = pred[]{{:T(512)}} compare(%gte.9, %constant.1), direction=LT, metadata={{op_name="jit(step)/shard_map/while/cond/lt"}}
}}

ENTRY %main (plane: s64[8], idx: s32[4], vals: u32[4]) -> s64[8] {{
  %plane = s64[8]{{0:T(1024)}} parameter(0), metadata={{op_name="state.accs['bids']"}}
  %idx = s32[4]{{0:T(1024)}} parameter(1)
  %vals = u32[4]{{0:T(1024)}} parameter(2)
  %zero = u32[]{{:T(128)}} constant(0)
  %custom-call.1 = u32[8]{{0:T(1024)}} custom-call(%plane), custom_call_target="X64SplitLow", metadata={{op_name="state.accs['bids']"}}
  %custom-call.2 = u32[8]{{0:T(1024)}} custom-call(%plane), custom_call_target="X64SplitHigh", metadata={{op_name="state.accs['bids']"}}
  %custom-call.9 = s32[4]{{0:T(1024)}} custom-call(%idx), custom_call_target="AssumeGatherIndicesInBound", metadata={{op_name="jit(step)/gather"}}
  %tuple.0 = (u32[]{{:T(128)}}, u32[8]{{0:T(1024)}}, u32[8]{{0:T(1024)}}, s32[4]{{0:T(1024)}}, u32[4]{{0:T(1024)}}) tuple(%zero, %custom-call.1, %custom-call.2, %custom-call.9, %vals)
  %while.1 = (u32[]{{:T(128)}}, u32[8]{{0:T(1024)}}, u32[8]{{0:T(1024)}}, s32[4]{{0:T(1024)}}, u32[4]{{0:T(1024)}}) while(%tuple.0), condition=%cond, body=%body, metadata={{op_name="jit(step)/shard_map/while"}}
  %gte.5 = u32[8]{{0:T(1024)}} get-tuple-element(%while.1), index=1
  %gte.6 = u32[8]{{0:T(1024)}} get-tuple-element(%while.1), index=2
  ROOT %custom-call.3 = s64[8]{{0:T(1024)}} custom-call(%gte.5, %gte.6), custom_call_target="X64Combine"
}}
"""


@pytest.mark.parametrize("instruction,region", [
    # a fusion that carries no name of its own takes what the
    # instructions of its fused computation agree on; the innermost of
    # the nested names is the region
    ("fusion.8", "fold.sum"),
    ("gather.2", "probe.window0"),
    ("sort.5", "probe.tail"),
    ("custom-call.1", "x64.split"),
    ("custom-call.2", "x64.split"),
    ("custom-call.3", "x64.join"),
    # the halves of a 64-bit column's scatter carry no name path: its
    # result reaches only an all-to-all under mesh.exchange, through the
    # move the compiler put between them, which goes the same way
    ("fusion.7", "exchange.pack"),
    ("copy-start", "exchange.pack"),
    ("copy-done", "exchange.pack"),
    # one that reaches two regions is nobody's
    ("scatter.two", UNNAMED),
    ("all-to-all.1", "exchange.collective"),
    # directly under mesh.exchange and no collective: packing
    ("reshape.8", "exchange.pack"),
    ("select.3", "exchange.pack"),
    ("pmax", "mesh.sync"),
    # a name path without a region scope, and another custom call
    ("add.9", UNNAMED),
    ("lt.1", UNNAMED),
    ("custom-call.9", UNNAMED),
    # a wrapper and the plumbing have no entry at all
    ("while.1", None),
    ("tuple.1", None),
    ("gte.5", None),
    ("plane", None),
    # nor has the inside of a fusion: the device runs the fusion
    ("scatter-add.7", None),
])
def test_classify_hlo(instruction, region):
    assert classify_hlo(_HLO).get(instruction) == region


#: the retire, cut down: the x64 rewriter turns the 64-bit row write
#: into writes of the halves and leaves them a bare name, between the
#: plane's split and its join
_RETIRE = """HloModule jit_retire, is_scheduled=true

%fused_select (p: u32[16,8], r: s32[]) -> u32[1,8] {
  %p = u32[16,8]{1,0} parameter(0)
  %r = s32[]{:T(128)} parameter(1)
  %zero = u32[]{:T(128)} constant(0)
  ROOT %broadcast.1 = u32[1,8]{1,0} broadcast(%zero), dimensions={}
}

ENTRY %main (plane: s64[16,8], row: s32[]) -> s64[16,8] {
  %plane = s64[16,8]{1,0} parameter(0), metadata={op_name="accs[\'bids\']"}
  %row = s32[]{:T(128)} parameter(1)
  %select_n.0 = s32[]{:T(128)} select(%row, %row, %row), metadata={op_name="jit(retire)/fire.retire/select_n"}
  %lo = u32[16,8]{1,0} custom-call(%plane), custom_call_target="X64SplitLow", metadata={op_name="accs[\'bids\']"}
  %hi = u32[16,8]{1,0} custom-call(%plane), custom_call_target="X64SplitHigh", metadata={op_name="accs[\'bids\']"}
  %select_fusion.lo = u32[1,8]{1,0} fusion(%lo, %select_n.0), kind=kLoop, calls=%fused_select, metadata={op_name="select.20"}
  %dus.lo = u32[16,8]{1,0} dynamic-update-slice(%lo, %select_fusion.lo, %select_n.0, %select_n.0), metadata={op_name="select.20"}
  %select_fusion.hi = u32[1,8]{1,0} fusion(%hi, %select_n.0), kind=kLoop, calls=%fused_select, metadata={op_name="select.21"}
  %dus.hi = u32[16,8]{1,0} dynamic-update-slice(%hi, %select_fusion.hi, %select_n.0, %select_n.0), metadata={op_name="select.21"}
  %copy.9 = u32[16,8]{1,0} copy(%dus.hi)
  ROOT %join = s64[16,8]{1,0} custom-call(%dus.lo, %copy.9), custom_call_target="X64Combine", metadata={op_name="custom-call.4"}
}
"""


@pytest.mark.parametrize("instruction,region", [
    ("lo", "x64.split"), ("hi", "x64.split"), ("join", "x64.join"),
    # a move between memory spaces that only a join reads is the join's
    ("copy.9", "x64.join"),
    # what computes between them is not: the row writes reach nothing
    # but the join and are fed by the scope's own row index
    ("select_fusion.lo", "fire.retire"), ("dus.lo", "fire.retire"),
    ("select_fusion.hi", "fire.retire"), ("dus.hi", "fire.retire"),
])
def test_a_split_or_a_join_gives_its_region_to_moves_only(instruction,
                                                          region):
    assert classify_hlo(_RETIRE).get(instruction) == region


def test_a_pathless_instruction_in_a_loop_takes_the_loops_region():
    """What reaches two regions and is fed by none is nobody's, unless
    the loop that holds it lies in a region: then it is the loop's (what
    the compiler adds inside a probe loop's body to move an operand
    between memory spaces reaches nothing but the body's root)."""
    assert classify_hlo(_HLO)["scatter.two"] == UNNAMED
    hlo = _HLO.replace('op_name="jit(step)/shard_map/while"',
                       'op_name="jit(step)/probe.tail/while"')
    assert classify_hlo(hlo)["scatter.two"] == "probe.tail"
    assert classify_hlo(hlo)["fusion.7"] == "exchange.pack"


def test_every_named_scope_in_the_package_is_of_the_vocabulary():
    root = pathlib.Path(__file__).resolve().parents[1] / "flink_tpu"
    found = set()
    for path in root.rglob("*.py"):
        for arg in re.findall(r"jax\.named_scope\(\s*(.+?)\)\s*[:,\n]",
                              path.read_text(encoding="utf-8")):
            names = re.findall(r'"([\w.{}]+)"', arg)
            assert names, f"{path}: named_scope({arg}) names no literal"
            for name in names:
                if "{kind}" in name:
                    found |= {name.format(kind=k)
                              for k in ("sum", "count", "min", "max")}
                else:
                    found.add(name)
    vocabulary = set(REGION_SCOPES) | PATH_SCOPES
    assert found <= vocabulary, sorted(found - vocabulary)
    # and no name of the vocabulary is without a scope in the source
    assert vocabulary <= found, sorted(vocabulary - found)


def test_program_regions_names_every_audited_program():
    """After a tiny Q5 job on the CPU (device-born, host-born, fused and
    through the mesh): every program of the audit that can be lowered
    again has a map under its HLO module's name, the host-born probe
    (a module-level jit, recorded by the backend's hot dispatch) among
    them, and the maps hold the regions the programs are made of."""
    from flink_tpu.analysis.jaxpr_rules import exercise_programs

    clear_program_audit()
    try:
        scopes = exercise_programs()
        assert "state.probe" in scopes and "mesh.step" in scopes
        maps = program_regions()
        by_module: dict = {}          # two fires share jit_fire_fn
        for key, regions in maps.items():
            by_module.setdefault(re.sub(r"\(\d*\)$", "", key),
                                 set()).update(regions.values())
        for entry in PROGRAM_AUDIT:
            fn = entry.fn
            if getattr(fn, "lower", None) is None:
                continue
            name = getattr(fn, "__name__", None)
            if name in (None, "dispatch"):    # a per-mesh dispatcher
                continue
            assert f"jit_{name}" in by_module, (entry.scope, name)
        for module, want in [
                ("jit_lookup_or_insert", {"probe.tail"}),
                ("jit_fold", {"fold.row", "fold.count", "fold.sum"}),
                ("jit_fire_fn", {"fire.merge", "fire.topk"}),
                ("jit_reset", {"fire.reset"}),
                ("jit_step", {"mesh.plan", "mesh.sync", "exchange.pack",
                              "probe.tail", "fold.row", "fold.count"})]:
            assert want <= by_module[module], module
        assert all(re.fullmatch(r"[\w.\-]+\(\d*\)", key) for key in maps)
    finally:
        clear_program_audit()
