"""Operation and byte counts, against hand-worked numbers for qwen2-7b."""
import jax
import pytest

from bench import costs, model

QWEN = model.model_config(model.load_config("qwen2-7b"))


def test_weight_bytes_match_the_chip():
    # what jax.tree.leaves(eng.params) sums to for qwen2-7b on a TPU v5e
    assert costs.weight_bytes(QWEN) == 3_820_138_496


def test_weight_bytes_match_the_program_tree():
    from repro.models import transformer as T
    tree = T.init_params(QWEN, mode="abstract", quantized=True, pack=True)
    total = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))
    assert costs.weight_bytes(QWEN) == total


def test_packed_linear_bytes_by_hand():
    # w_gate: 3584 x 18944 int4 -> 33,947,648 bytes + 18944 x 8 scale/zero
    assert costs.packed_linear_bytes(3584, 18944, 4) == 34_099_200
    # lm_head: int8, vocab padded to 151808
    assert costs.packed_linear_bytes(3584, 151646, 8) == 545_294_336
    # glm4-9b w_gate: d_ff 13696 pads to 13824 output columns
    assert costs.packed_linear_bytes(4096, 13696, 4) == \
        4096 * 13824 // 2 + 8 * 13824


def test_one_decode_step_by_hand():
    # one token: 2 x (q 3584^2 + k,v 2 x 3584 x 512 + o 3584^2
    # + 3 x 3584 x 18944) x 28 layers
    per_layer = 3584 * 3584 * 2 + 2 * 3584 * 512 + 3 * 3584 * 18944
    assert costs.token_linear_ops(QWEN) == 2 * 28 * per_layer
    assert costs.head_ops(QWEN) == 2 * 3584 * 151646
    # attention over 1000 keys: 4 x 28 heads x 128 x 1000 per layer
    assert costs.attention_ops(QWEN, 1000) == 4 * 28 * 28 * 128 * 1000
    # one row at 1000 tokens of context: int8 K + fp8 V + 8 bytes of key
    # scale and zero per kv head, 28 layers, plus the f32 query and output
    assert costs.decode_attention_bytes(QWEN, 1000, 1) == \
        28 * (1000 * 4 * (2 * 128 + 8) + 2 * 4 * 28 * 128)


def test_w4a8_call_by_hand():
    ops, nbytes = costs.w4a8_call(8, 3584, 512, 4)
    assert ops == 2 * 8 * 3584 * 512
    assert nbytes == 8 * 3584 + 3584 * 256 + 4 * 8 + 8 * 512 + 4 * 8 * 512
    # the same from the shapes a trace names
    text = ('%closed_call.99 = f32[8,512]{1,0:T(8,128)S(1)} custom-call('
            's8[8,3584] %a, s8[3584,256] %b), custom_call_target='
            '"tpu_custom_call", operand_layout_constraints={s8[8,3584]{1,0},'
            ' s8[3584,256]{1,0}, f32[8,1]{1,0}, f32[1,512]{1,0}, '
            'f32[1,512]{1,0}}')
    calls = [("w4a8_matmul", "decode", text, 1e-5)]
    peaks = {"int8_ops": 393e12, "hbm_bytes_per_s": 819e9}
    share = costs.w4a8_roofline(calls, "decode", peaks)
    assert share == pytest.approx(100 * nbytes / 819e9 / 1e-5)
    assert costs.w4a8_roofline(calls, "prefill", peaks) is None
