"""HLO text parsing: shape/byte arithmetic, collective traffic factors,
and the text-level cost model the workload audit falls back on."""

from __future__ import annotations

import textwrap

from repro.analysis.hlo import (_shape_bytes, _shape_numel, parse_collectives,
                                parse_hlo_cost, parse_hlo_ops)

# ---------------------------------------------------------------------------
# _shape_bytes / _shape_numel
# ---------------------------------------------------------------------------


def test_shape_bytes_simple_array():
    assert _shape_bytes("f32[4,8]{1,0}") == 4 * 8 * 4


def test_shape_bytes_tuple_sums_subshapes():
    assert _shape_bytes("(f32[4]{0}, f32[4]{0})") == 2 * 4 * 4


def test_shape_bytes_mixed_dtypes():
    assert _shape_bytes("(bf16[8]{0}, s32[2]{0})") == 8 * 2 + 2 * 4


def test_shape_bytes_scalar():
    # a scalar f32[] has one element
    assert _shape_bytes("f32[]") == 4


def test_shape_bytes_unknown_dtype_skipped():
    assert _shape_bytes("token[]") == 0
    assert _shape_bytes("(f32[4]{0}, token[])") == 16


def test_shape_bytes_fp8_one_byte_each():
    # quantized ops must not land in the unhandled tally
    assert _shape_bytes("f8e4m3fn[16,8]{1,0}") == 16 * 8
    assert _shape_bytes("f8e5m2[32]{0}") == 32
    assert _shape_bytes("(f8e4m3fnuz[4]{0}, f8e8m0fnu[4]{0})") == 8


def test_fp8_convert_costed_not_unhandled():
    text = "  %c = f8e4m3fn[64]{0} convert(f32[64]{0} %x)\n"
    cost = parse_hlo_cost(text)
    assert cost.unhandled == {}
    assert cost.bytes_by_op["convert"] == 64 * 1 + 64 * 4


def test_shape_numel_counts_unknown_dtypes():
    # numel is a structural count: unknown dtypes still contribute
    assert _shape_numel("(f32[4]{0}, u4[8]{0})") == 12
    assert _shape_numel("f32[]") == 1


# ---------------------------------------------------------------------------
# parse_collectives
# ---------------------------------------------------------------------------

AR_LINE = ("  %ar = f32[1024]{0} all-reduce(%x), "
           "replica_groups={{0,1,2,3}}, to_apply=%add\n")


def test_all_reduce_ring_factor():
    stats = parse_collectives(AR_LINE, n_devices=4)
    assert stats.count_by_op == {"all-reduce": 1}
    assert stats.bytes_by_op["all-reduce"] == 2.0 * 1024 * 4 * (3 / 4)


def test_group_size_from_iota_groups():
    line = ("  %ag = bf16[16,64]{1,0} all-gather(%x), "
            "replica_groups=[2,8], dimensions={0}\n")
    stats = parse_collectives(line, n_devices=999)
    assert stats.bytes_by_op["all-gather"] == 16 * 64 * 2 * (7 / 8)


def test_missing_replica_groups_uses_default():
    line = "  %cp = f32[256]{0} collective-permute(%x)\n"
    stats = parse_collectives(line, n_devices=2)
    assert stats.bytes_by_op["collective-permute"] == 256 * 4


def test_single_device_is_free():
    # n<=1 means no cross-device traffic at all
    stats = parse_collectives(AR_LINE.replace("{{0,1,2,3}}", "{{0}}"),
                              n_devices=1)
    assert stats.total_bytes == 0
    assert stats.total_count == 0


def test_async_done_not_double_counted():
    text = (
        "  %s = f32[64]{0} all-reduce-start(%x), replica_groups={{0,1}}\n"
        "  %d = f32[64]{0} all-reduce-done(%s)\n"
    )
    stats = parse_collectives(text, n_devices=2)
    assert stats.count_by_op == {"all-reduce": 1}


def test_empty_module_summary():
    stats = parse_collectives("HloModule empty\n", n_devices=8)
    assert stats.total_bytes == 0
    assert stats.summary() == "none"


# ---------------------------------------------------------------------------
# parse_hlo_cost (the audit's text-level fallback)
# ---------------------------------------------------------------------------

DOT_MODULE = textwrap.dedent("""\
    HloModule dot

    ENTRY %main (a: f32[64,32], b: f32[32,48]) -> f32[64,48] {
      %a = f32[64,32]{1,0} parameter(0)
      %b = f32[32,48]{1,0} parameter(1)
      ROOT %dot = f32[64,48]{1,0} dot(f32[64,32]{1,0} %a, f32[32,48]{1,0} %b), lhs_contracting_dims={1}, rhs_contracting_dims={0}
    }
""")


def test_dot_flops_are_2mnk():
    cost = parse_hlo_cost(DOT_MODULE)
    assert cost.flops == 2 * 64 * 48 * 32      # 196608
    assert cost.flops_by_op == {"dot": 196608.0}
    # operands + result traffic
    assert cost.bytes_by_op["dot"] == (64 * 32 + 32 * 48 + 64 * 48) * 4
    assert cost.unhandled == {}


def test_bare_operand_names_resolve_through_symbol_table():
    # newer XLA prints operands as bare names; their shapes come from the
    # computation's own definitions
    bare = DOT_MODULE.replace("f32[64,32]{1,0} %a,", "%a,") \
        .replace("f32[32,48]{1,0} %b)", "%b)")
    assert "dot(%a, %b)" in bare
    for parsed in (parse_hlo_cost(bare), parse_hlo_ops(bare)):
        assert parsed.flops == 2 * 64 * 48 * 32
        assert parsed.bytes_accessed == (64 * 32 + 32 * 48 + 64 * 48) * 4


def test_symbol_table_is_per_computation():
    text = textwrap.dedent("""\
        %other (x: f32[1000]) -> f32[1000] {
          %x = f32[1000]{0} parameter(0)
          ROOT %n = f32[1000]{0} negate(%x)
        }

        ENTRY %main (x: f32[8]) -> f32[8] {
          %x = f32[8]{0} parameter(0)
          ROOT %n.1 = f32[8]{0} negate(%x)
        }
    """)
    ops = parse_hlo_ops(text)
    assert ops.by_name()["n.1"].bytes_accessed == (8 + 8) * 4


# TPU-compiled text: tiled layouts nest parens inside shapes, matmuls are
# convolutions, and a scanned loop is a tuple-shaped while
TPU_MODULE = textwrap.dedent("""\
    %body (p: (s32[], f32[64,32])) -> (s32[], f32[64,32]) {
      %p = (s32[]{:T(128)}, f32[64,32]{1,0:T(8,128)}) parameter(0)
      ROOT %t = (s32[]{:T(128)}, f32[64,32]{1,0:T(8,128)}) tuple(%p)
    }

    ENTRY %main.4 (a.1: f32[64,32], b.1: f32[32,48]) -> f32[64,48] {
      %a.1 = f32[64,32]{1,0:T(8,128)} parameter(0)
      %b.1 = f32[32,48]{1,0:T(8,128)} parameter(1)
      %convolution.5 = f32[64,48]{1,0:T(8,128)S(1)} convolution(%a.1, %b.1), dim_labels=bf_io->bf, metadata={op_name="jit(f)/dot_general"}
      %copy-start.1 = (f32[64,32]{1,0:T(8,128)S(1)}, f32[64,32]{1,0:T(8,128)}, u32[]{:S(2)}) copy-start(%a.1)
      %tuple.13 = (s32[]{:T(128)}, f32[64,32]{1,0:T(8,128)}) tuple(%a.1)
      ROOT %while = (s32[]{:T(128)}, f32[64,32]{1,0:T(8,128)}) while(%tuple.13), condition=%cond, body=%body
    }
""")


def test_tpu_layouts_tuples_and_convolution_matmuls():
    ops = parse_hlo_ops(TPU_MODULE).by_name()
    assert {"convolution.5", "copy-start.1", "while"} <= set(ops)
    conv = ops["convolution.5"]
    assert conv.flops == 2 * 64 * 48 * 32
    assert conv.bytes_accessed == (64 * 32 + 32 * 48 + 64 * 48) * 4
    assert parse_hlo_cost(TPU_MODULE).flops_by_op == {
        "convolution": 2 * 64 * 48 * 32}


def test_elementwise_one_flop_per_element():
    text = "  %add = f32[128]{0} add(f32[128]{0} %x, f32[128]{0} %y)\n"
    cost = parse_hlo_cost(text)
    assert cost.flops == 128
    assert cost.bytes_accessed == (128 + 128 + 128) * 4


def test_copy_ops_move_bytes_but_no_flops():
    text = "  %t = f32[8,16]{0,1} transpose(%x), dimensions={1,0}\n"
    cost = parse_hlo_cost(text)
    assert cost.flops == 0
    assert cost.bytes_by_op["transpose"] > 0


def test_structural_ops_are_free():
    text = textwrap.dedent("""\
        %p = f32[4]{0} parameter(0)
        %t = (f32[4]{0}, f32[4]{0}) tuple(%p, %p)
        %g = f32[4]{0} get-tuple-element(%t), index=0
    """)
    cost = parse_hlo_cost(text)
    assert cost.flops == 0
    assert cost.bytes_accessed == 0
    assert cost.unhandled == {}


def test_unhandled_ops_are_tallied_not_costed():
    text = "  %s = f32[4,4]{1,0} cholesky(%x)\n"
    cost = parse_hlo_cost(text)
    assert cost.unhandled == {"cholesky": 1}
    assert "unhandled" in cost.summary()


def test_compiled_jax_dot_matches_formula():
    import pytest
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    a = jax.ShapeDtypeStruct((64, 32), jnp.float32)
    b = jax.ShapeDtypeStruct((32, 48), jnp.float32)
    text = jax.jit(jnp.dot).lower(a, b).compile().as_text()
    cost = parse_hlo_cost(text)
    assert cost.flops_by_op.get("dot") == 2 * 64 * 48 * 32


# ---------------------------------------------------------------------------
# parse_hlo_ops (per-op records for roofline attribution)
# ---------------------------------------------------------------------------


def test_per_op_records_on_dot_module():
    mod = parse_hlo_ops(DOT_MODULE)
    # parameters are structural; only the dot yields a record
    assert [op.name for op in mod.ops] == ["dot"]
    dot = mod.by_name()["dot"]
    assert dot.kind == "dot"
    assert dot.flops == 2 * 64 * 48 * 32
    assert dot.bytes_accessed == (64 * 32 + 32 * 48 + 64 * 48) * 4
    assert dot.modeled
    assert mod.unhandled == {}
    # module totals agree with the flattened parser on a fusion-free module
    cost = parse_hlo_cost(DOT_MODULE)
    assert mod.flops == cost.flops
    assert mod.bytes_accessed == cost.bytes_accessed


FUSED_MODULE = textwrap.dedent("""\
    HloModule fused

    %fused_computation (p0: f32[128], p1: f32[128]) -> f32[128] {
      %p0 = f32[128]{0} parameter(0)
      %p1 = f32[128]{0} parameter(1)
      %add.1 = f32[128]{0} add(f32[128]{0} %p0, f32[128]{0} %p1)
      ROOT %tanh.2 = f32[128]{0} tanh(f32[128]{0} %add.1)
    }

    ENTRY %main (a: f32[128], b: f32[128]) -> f32[128] {
      %a = f32[128]{0} parameter(0)
      %b = f32[128]{0} parameter(1)
      ROOT %fusion = f32[128]{0} fusion(f32[128]{0} %a, f32[128]{0} %b), kind=kLoop, calls=%fused_computation
    }
""")


def test_fusion_cost_rolled_up_from_called_computation():
    mod = parse_hlo_ops(FUSED_MODULE)
    assert [op.name for op in mod.ops] == ["fusion"]
    fusion = mod.ops[0]
    assert fusion.kind == "fusion"
    assert fusion.flops == 128 + 128          # add + tanh, one per element
    assert fusion.bytes_accessed > 0
    assert fusion.modeled
    # fusion-body parameters must not pollute the unhandled tally
    assert mod.unhandled == {}


def test_reduce_costed_per_input_element():
    text = textwrap.dedent("""\
        ENTRY %main (x: f32[64,32]) -> f32[64] {
          %x = f32[64,32]{1,0} parameter(0)
          %c = f32[] constant(0)
          ROOT %reduce.1 = f32[64]{0} reduce(f32[64,32]{1,0} %x, f32[] %c), dimensions={1}, to_apply=%add
        }
    """)
    mod = parse_hlo_ops(text)
    red = mod.by_name()["reduce.1"]
    # one combiner application per input element (+1 for the init scalar)
    assert red.flops == 64 * 32 + 1
    assert red.kind == "reduce"


def test_while_body_counted_once_and_flagged():
    text = textwrap.dedent("""\
        %body (p: f32[16]) -> f32[16] {
          %p = f32[16]{0} parameter(0)
          ROOT %add.b = f32[16]{0} add(f32[16]{0} %p, f32[16]{0} %p)
        }

        %cond (p: f32[16]) -> pred[] {
          %p = f32[16]{0} parameter(0)
          ROOT %lt = pred[] compare(f32[16]{0} %p, f32[16]{0} %p), direction=LT
        }

        ENTRY %main (x: f32[16]) -> f32[16] {
          %x = f32[16]{0} parameter(0)
          ROOT %while.1 = f32[16]{0} while(f32[16]{0} %x), condition=%cond, body=%body
        }
    """)
    mod = parse_hlo_ops(text)
    wh = mod.by_name()["while.1"]
    assert wh.flops == 16                     # body counted exactly once
    assert mod.unhandled == {"while": 1}      # trip count unknown -> partial


def test_unmodeled_op_keeps_record_with_zero_cost():
    text = textwrap.dedent("""\
        ENTRY %main (x: f32[4,4]) -> f32[4,4] {
          %x = f32[4,4]{1,0} parameter(0)
          ROOT %cholesky.1 = f32[4,4]{1,0} cholesky(f32[4,4]{1,0} %x)
        }
    """)
    mod = parse_hlo_ops(text)
    op = mod.by_name()["cholesky.1"]
    assert not op.modeled                     # record exists for time-joins
    assert op.flops == 0 and op.bytes_accessed == 0
    assert mod.unhandled == {"cholesky": 1}


def test_op_intensity_edge_cases():
    mod = parse_hlo_ops(DOT_MODULE)
    dot = mod.by_name()["dot"]
    assert dot.intensity == dot.flops / dot.bytes_accessed
    from repro.analysis.hlo import OpCost
    assert OpCost("x", "exp", flops=4.0, bytes_accessed=0.0).intensity \
        == float("inf")
    assert OpCost("c", "copy", flops=0.0, bytes_accessed=8.0).intensity == 0.0
    assert OpCost("t", "tuple", flops=0.0, bytes_accessed=0.0).intensity == 0.0


def test_compiled_module_parses_per_op():
    import pytest
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    a = jax.ShapeDtypeStruct((64, 32), jnp.float32)
    b = jax.ShapeDtypeStruct((32, 48), jnp.float32)
    text = jax.jit(jnp.dot).lower(a, b).compile().as_text()
    mod = parse_hlo_ops(text)
    dots = [op for op in mod.ops if op.kind == "dot"]
    assert sum(op.flops for op in dots) == 2 * 64 * 48 * 32
