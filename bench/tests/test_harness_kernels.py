"""Kernel operation and byte counts from the shapes in a trace event's HLO
text, against counts made by hand at small shapes."""
import _paths  # noqa: F401
import pytest

from harness import hlo, readers

KERNELS = readers.kernel_modules()


def test_int8_matmul_counts_by_hand():
    text = ("%int8_matmul.3 = f32[4,6]{1,0:T(8,128)S(1)} custom-call("
            "s8[4,2]{1,0:T(8,128)(4,1)S(1)} %a, s8[2,6]{1,0:T(8,128)(4,1)} "
            "%b, f32[4,1]{1,0:T(8,128)} %c, f32[1,6]{1,0:T(1,128)} %d), "
            'custom_call_target="tpu_custom_call", operand_layout_'
            "constraints={s8[4,2]{1,0}, s8[2,6]{1,0}}")
    ops, nbytes = KERNELS["int8_matmul"].cost(*hlo.shapes(text))
    # 4 x 6 outputs, each 2 multiply-adds
    assert ops == 4 * 6 * 2 * 2
    # x 8 B + w 12 B + scales 16 + 24 B + out 96 B
    assert nbytes == 8 + 12 + 16 + 24 + 96


def test_tree_attention_counts_by_hand():
    text = ("%tree_attention_partial.1 = (f32[1,2,6,4]{3,2,1,0}, "
            "f32[1,2,6,1]{3,2,1,0}, f32[1,2,6,1]{3,2,1,0}) custom-call("
            "bf16[1,2,6,4]{3,2,1,0} %q, bf16[1,2,3,4]{3,2,1,0} %k, "
            "bf16[1,2,3,4]{3,2,1,0} %v, s32[1,6,3]{2,1,0} %m), "
            'custom_call_target="tpu_custom_call"')
    ops, nbytes = KERNELS["tree_attention"].cost(*hlo.shapes(text))
    # per (batch, kv head): 6 rows x 3 keys x 4 dims, scores and mix
    assert ops == 2 * (6 * 3 * 4 * 2) * 2
    reads = 1 * 2 * 6 * 4 * 2 + 2 * (1 * 2 * 3 * 4 * 2) + 1 * 6 * 3 * 4
    writes = 1 * 2 * 6 * 4 * 4 + 2 * (1 * 2 * 6 * 1 * 4)
    assert nbytes == reads + writes


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_every_kernel_names_its_trace_op_and_peak(name):
    mod = KERNELS[name]
    assert isinstance(mod.TRACE_NAME, str) and mod.TRACE_NAME
    assert mod.PEAK in ("bf16_flops_s", "int8_ops_s")


def test_shapes_of_a_traced_instruction():
    text = ("%int8_matmul.32 = f32[256,12288]{1,0:T(8,128)S(1)} custom-call("
            "s8[256,3072]{1,0:T(8,128)(4,1)S(1)} %clamp_convert_fusion.4, "
            "s8[3072,12288]{1,0:T(8,128)(4,1)S(1)} %fusion.502, "
            "f32[256,1]{1,0:T(8,128)S(1)} %reshape.934, "
            "f32[1,12288]{1,0:T(1,128)S(1)} %bitcast.724), "
            'custom_call_target="tpu_custom_call", operand_layout_'
            "constraints={s8[256,3072]{1,0}, s8[3072,12288]{1,0}}")
    res, ops = hlo.shapes(text)
    assert res == [("f32", (256, 12288))]
    assert ops == [("s8", (256, 3072)), ("s8", (3072, 12288)),
                   ("f32", (256, 1)), ("f32", (1, 12288))]
