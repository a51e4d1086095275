"""The FLOP and byte functions against counts worked out by hand."""

import pytest

from chipbench import flops

# d 4, 2 query heads and 1 KV head of 2, 4 experts of f 3, top-2, 1 shared
MOE = dict(d_model=4, num_heads=2, num_kv_heads=1, head_dim=2, num_experts=4, top_k=2,
           d_ff_expert=3, num_shared_experts=1, vocab_size=10, num_layers=2)
DENSE = dict(d_model=4, num_heads=2, num_kv_heads=1, head_dim=2, d_ff=5, vocab_size=10, num_layers=3)


def test_layer_matmul_params():
    # attention: q 4*2*2=16, k and v 2*4*1*2=16, o 2*2*4=16 -> 48
    # MoE: router 4*4=16, (top-2 + 1 shared) * 3 matrices * 4*3 = 108 -> 124
    assert flops.layer_matmul_params(MOE) == 48 + 124
    # dense: 48 + 3*4*5 = 108
    assert flops.layer_matmul_params(DENSE) == 108


def test_token_and_prompt_flops():
    # per layer 2*172 + 4*2*2*ctx; ctx 5 -> 344 + 80 = 424; 2 layers; head 2*10*4 = 80
    assert flops.token_flops(MOE, 5, head=True) == 2 * 424 + 80
    assert flops.token_flops(MOE, 5, head=False) == 2 * 424
    # prompt of 3: per layer 2*172*3 + 4*2*2*(1+2+3) = 1032 + 96; 2 layers; one head
    assert flops.prompt_flops(MOE, 3) == 2 * (1032 + 96) + 80


def test_paged_attention_call():
    # lengths 1 and 17 at page 16: QK+PV 4*2*2*18 = 288 FLOPs
    # bytes: K+V pages (1 and 2 pages of 16 rows * 1 head * 2 dims * 2 B) = 2*16*4*(1+2) = 384,
    # plus query and output rows 2*2*2*2 = 16 per slot
    f, b = flops.paged_attention_call(DENSE, [1, 17], page_size=16)
    assert f == 288 and b == 384 + 32


def test_expert_ffn_call():
    # tokens 2, 0, 1, 0 over 4 experts of d 4, f 3: 2*3*4*3 = 72 FLOPs a token -> 216;
    # weights of the 2 experts hit: 2 * 3*4*3*2 B = 144; tokens in and out 3 * 2*4*2 B = 48
    f, b = flops.expert_ffn_call(MOE, [2, 0, 1, 0])
    assert f == 216 and b == 144 + 48


@pytest.mark.parametrize("f,b,want", [(197e12, 0.0, 1.0), (0.0, 819e9, 1.0), (197e12, 1638e9, 2.0)])
def test_roofline_seconds(f, b, want):
    hw = {"peak_bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    assert flops.roofline_seconds(f, b, hw) == pytest.approx(want)
