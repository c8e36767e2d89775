"""`scripts/loop_body_ops.py`'s reading of a compiled program's text:
which computation is the innermost loop body, what counts as an
instruction with a cost estimate, and how a result is written. Text
only; what the TPU's compiler makes of Ouro's decode is asserted in
`tests/test_flash_kernel_v5e.py`."""

import pytest


def cost(cycles):
    return f'backend_config={{"window_config":{{"estimated_cycles":"{cycles}"}}}}'


# an outer loop over an inner one, as a compiled decode has them, with one of each
# kind of line the inner body holds
TEXT = f"""HloModule jit_decode, is_scheduled=true

%fused_computation.1 (param_0.1: bf16[48,2048,6144], param_1.1: s32[]) -> bf16[6144] {{
  %param_0.1 = bf16[48,2048,6144]{{2,1,0:T(8,128)(2,1)}} parameter(0)
  ROOT %dot.1 = bf16[6144]{{0:T(1024)(128)(2,1)}} dot(%param_0.1, %param_0.1), {cost(9)}
}}

%inner_body (arg: (s32[], f32[1,2048])) -> (s32[], f32[1,2048]) {{
  %arg = (s32[]{{:T(128)}}, f32[1,2048]{{1,0:T(1,128)S(1)}}) parameter(0)
  %get-tuple-element.1 = s32[]{{:T(128)}} get-tuple-element(%arg), index=0
  %constant.1 = s32[]{{:T(128)}} constant(1)
  %fusion.41 = bf16[6144]{{0:T(1024)(128)(2,1)S(1)}} fusion(%get-tuple-element.1), kind=kOutput, calls=%fused_computation.1, metadata={{op_name="jit(decode)/while/body/layer/attn/dot_general" stack_frame_id=61}}, {cost(176844)}
  %bitcast.3 = bf16[48,128]{{1,0:T(8,128)(2,1)}} bitcast(%fusion.41)
  %slice_reduce_fusion.4 = (bf16[2048]{{0:T(1024)(128)(2,1)S(1)}}, bf16[2048]{{0:T(1024)(128)(2,1)S(1)}}, /*index=2*/bf16[2048]{{0:T(1024)(128)(2,1)S(1)}}) fusion(%fusion.41), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="jit(decode)/while/body/layer/attn/split"}}, {cost(2059)}
  %rsqrt.48 = f32[]{{:T(128)S(6)}} rsqrt(%constant.1), metadata={{op_name="jit(decode)/while/body/layer/attn/rsqrt"}}, backend_config={{"flag_configs":[]}}
  %decode_attention.9 = bf16[16,1,128]{{2,1,0:T(2,128)(2,1)S(1)}} custom-call(%bitcast.3), custom_call_target="tpu_custom_call", metadata={{op_name="jit(decode)/layer/attn/jit(decode_attention)/decode_attention/pallas_call"}}
  %add.1 = s32[]{{:T(128)}} add(%get-tuple-element.1, %constant.1), metadata={{op_name="jit(decode)/while/body/add"}}
  ROOT %tuple.1 = (s32[]{{:T(128)}}, f32[1,2048]{{1,0:T(1,128)S(1)}}) tuple(%add.1, %arg)
}}

%inner_cond (arg.1: (s32[], f32[1,2048])) -> pred[] {{
  %arg.1 = (s32[]{{:T(128)}}, f32[1,2048]{{1,0:T(1,128)S(1)}}) parameter(0)
  ROOT %lt.1 = pred[]{{:T(512)}} compare(%arg.1, %arg.1), direction=LT
}}

%outer_body (arg.2: (s32[], f32[1,2048])) -> (s32[], f32[1,2048]) {{
  %arg.2 = (s32[]{{:T(128)}}, f32[1,2048]{{1,0:T(1,128)S(1)}}) parameter(0)
  %copy.9 = f32[1,2048]{{1,0:T(1,128)S(1)}} copy(%arg.2), {cost(2000)}
  %copy.10 = f32[1,2048]{{1,0:T(1,128)S(1)}} copy(%copy.9), {cost(2000)}
  ROOT %while.104 = (s32[]{{:T(128)}}, f32[1,2048]{{1,0:T(1,128)S(1)}}) while(%arg.2), condition=%inner_cond, body=%inner_body
}}

ENTRY %main (p: f32[1,2048]) -> f32[1,2048] {{
  %p = f32[1,2048]{{1,0:T(1,128)}} parameter(0)
  %while.100 = (s32[]{{:T(128)}}, f32[1,2048]{{1,0:T(1,128)S(1)}}) while(%p), condition=%inner_cond, body=%outer_body
  ROOT %get-tuple-element.9 = f32[1,2048]{{1,0:T(1,128)}} get-tuple-element(%while.100), index=1
}}
"""


@pytest.fixture(scope="module")
def rows(loop_body_ops):
    return {row["name"]: row for row in loop_body_ops.body_rows(TEXT)}


def test_the_body_listed_is_the_loop_that_holds_no_loop(loop_body_ops, rows):
    assert list(rows) == [
        "fusion.41", "slice_reduce_fusion.4", "rsqrt.48", "decode_attention.9", "add.1"]
    assert [row["name"] for row in loop_body_ops.costed(rows.values())] == [
        "fusion.41", "slice_reduce_fusion.4"]


@pytest.mark.parametrize("name, op, result, cycles, origin", [
    ("fusion.41", "fusion", "bf16[6144]", 176844, "layer/attn/dot_general"),
    # a tuple's result is read whole: an instruction that has one still counts
    ("slice_reduce_fusion.4", "fusion", "(bf16[2048], bf16[2048], bf16[2048])", 2059,
     "layer/attn/split"),
    # scalar arithmetic in scalar memory and a kernel's call carry no estimate
    ("rsqrt.48", "rsqrt", "f32[]", None, "layer/attn/rsqrt"),
    ("decode_attention.9", "custom-call tpu_custom_call", "bf16[16,1,128]", None,
     "jit(decode_attention)/decode_attention/pallas_call"),
    ("add.1", "add", "s32[]", None, "while/body/add"),
])
def test_a_row_is_the_instructions_name_result_estimate_and_origin(
        rows, name, op, result, cycles, origin):
    row = rows[name]
    assert (row["op"], row["result"], row["cycles"], row["from"]) == (op, result, cycles, origin)


def test_a_text_without_a_loop_is_an_error(loop_body_ops):
    with pytest.raises(ValueError, match="no while loop"):
        loop_body_ops.body_rows(TEXT[:TEXT.index("%inner_body")])
