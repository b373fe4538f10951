"""The bytes function against hand arithmetic for the published config."""
import json

import model_bytes
from conftest import BENCH


def published():
    cfg = json.loads((BENCH / "configs" / "qwen25-1p5b.json").read_text())
    cfg.pop("bench")
    return cfg


def test_decode_tick_weight_bytes_of_qwen25_1p5b():
    # per layer: q 1536x1536, k and v 1536x256 each, o 1536x1536,
    # gate/up/down 3 x 1536x8960
    per_layer = 2_359_296 + 2 * 393_216 + 2_359_296 + 3 * 13_762_560
    assert per_layer == 46_792_704
    head = 1536 * 151_936
    assert head == 233_373_696
    want = 28 * per_layer + head
    assert want == 1_543_569_408
    assert model_bytes.decode_tick_weight_bytes(published()) == want
    assert model_bytes.decode_tick_weight_bytes(published(), 0.5) == want / 2


def test_kv_bytes_per_row():
    # 2 (k, v) x 28 layers x 2 kv heads x 128 x 2 bytes
    assert model_bytes.kv_bytes_per_row(published()) == 28_672


def test_flops_copies_agree_with_bench_vlm_arithmetic():
    cfg = published()
    assert model_bytes.lm_matmul_flops_per_token(cfg) == 2 * 1_543_569_408
    assert model_bytes.lm_attention_flops(cfg, 100) == 28 * 4.0 * 100 * 1536
    v = {"image_size": 224, "patch_size": 14, "vision_dim": 1280,
         "vision_layers": 32, "vision_ffn": 5120}
    p = 256
    per_layer = 2 * (4 * 1280 ** 2 + 3 * 1280 * 5120)
    want = p * (2 * 588 * 1280 + 32 * per_layer + 32 * 4.0 * p * 1280 + 2 * 1280 * 1536)
    assert model_bytes.vision_matmul_flops(v, 1536) == want
