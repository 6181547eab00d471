"""bench/flops.py against a hand count of the same terms, at the smoke
sizes of both configurations."""
import pytest

from bench.flops import flops_per_token

FIELDS = ("family", "num_layers", "d_model", "vocab_size", "num_heads",
          "num_kv_heads", "head_dim", "d_ff", "ssm_state", "ssm_expand",
          "ssm_head_dim", "ssm_chunk", "attn_every")


def smoke_model(arch):
    from repro import configs

    cfg = configs.smoke_config(arch)
    return {k: getattr(cfg, k) for k in FIELDS}


def test_mamba2_smoke_hand_count():
    # d 64, d_inner 128, N 16, H 8, 4 layers, Q 8, vocab 512
    # in_proj 64 x (2*128 + 2*16 + 8) = 18,944; out_proj 128 x 64 = 8,192
    # 4 layers: 108,544; head 64 x 512 = 32,768 -> 141,312 weights; x 6
    # SSD fwd per layer 2*8*16 + 2*8*128 + 4*16*128 = 10,496; x 3 x 4
    t = flops_per_token(smoke_model("mamba2_370m"), seq_len=32)
    assert t["matmul"] == 6 * 141_312 == 847_872
    assert t["ssd"] == 3 * 4 * 10_496 == 125_952
    assert t["attention"] == 0
    assert t["total"] == 973_824


def test_zamba2_smoke_hand_count():
    # mamba layers and head as above: 141,312; the shared block (width
    # 4 x 16 = 64, kv 64, d_ff 128): 2*64*64 + 2*64*64 + 3*64*128 = 40,960,
    # invoked 4 / 2 = 2 times -> 223,232 weights; x 6
    # attention at S = 32: 3 x 2 invocations x 4 * 32 * 64 = 49,152
    t = flops_per_token(smoke_model("zamba2_2p7b"), seq_len=32)
    assert t["matmul"] == 6 * 223_232 == 1_339_392
    assert t["ssd"] == 125_952
    assert t["attention"] == 49_152
    assert t["total"] == 1_514_496


@pytest.mark.parametrize("name,total", [("mamba2-370m", 2_518_155_264),
                                        ("zamba2-2.7b", 2_752_610_304)])
def test_benchmark_configs(name, total):
    import json
    from pathlib import Path

    cfg = json.loads((Path(__file__).resolve().parents[1] / "configs"
                      / f"{name}.json").read_text())
    assert flops_per_token(cfg["model"], 4096)["total"] == total
