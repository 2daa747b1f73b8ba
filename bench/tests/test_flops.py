"""Operation and byte counts against hand counts at a small config."""

from bench import flops

C = {"n_layers": 2, "d_model": 8, "n_heads": 4, "n_kv_heads": 2, "d_head": 4,
     "d_ff": 16, "vocab": 10}
PEAKS = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}


def test_dense_matmul_params_by_hand():
    # per layer: q 8*16 + k,v 2*(8*8) + o 16*8 = 384; mlp 3*8*16 = 384
    assert flops.dense_matmul_params(C) == 2 * (384 + 384) + 8 * 10


def test_token_and_request_flops_by_hand():
    assert flops.attention_flops(C, 5) == 4 * 2 * 4 * 4 * 5
    assert flops.token_flops(C, 5) == 2 * 1616 + 640
    # three tokens see 1, 2 and 3 keys
    want = sum(flops.token_flops(C, k) for k in (1, 2, 3))
    assert flops.request_flops(C, 3) == want


def test_flash_decode_call_by_hand():
    ops, nbytes = flops.flash_decode_call(C, batch=3, kv_len=7)
    assert ops == 4 * 3 * 4 * 4 * 7
    # q and out: 2 * 3*4*4 bf16; k and v: 2 * 3*2*4*7 bf16
    assert nbytes == 2 * 3 * 4 * 4 * 2 + 2 * 3 * 2 * 4 * 7 * 2


def test_roofline_seconds_takes_the_binding_peak():
    assert flops.roofline_seconds(1000, 10, PEAKS) == 10.0
    assert flops.roofline_seconds(100, 1000, PEAKS) == 100.0


def test_train_step_flops_by_hand():
    # 6 per weight and token; attention 3 x 4 x L x h x hd per query-key pair
    got = flops.train_step_flops(active_matmul_params=50, tokens=8, c=C, seq=4)
    pairs = 2 * (4 * 5 // 2)
    assert got == 6 * 50 * 8 + 3 * 4 * 2 * 4 * 4 * pairs
