"""The FLOP counts of the two configurations against counts by hand."""
import pytest

from bench import files


def _count(config: str, seq_len: int) -> float:
    cfg = files.load_json("configs", config)
    return files.load_module("references", cfg["reference"]).train_flops_per_token(cfg, seq_len)


def test_phi3_medium_one_layer_by_hand():
    # per layer: q 5120x5120, k and v 5120x1280 each, o 5120x5120,
    # SwiGLU 3 x 5120x17920; the untied head 32064x5120
    q = o = 5120 * 5120
    kv = 2 * 5120 * 1280
    mlp = 3 * 5120 * 17920
    head = 32064 * 5120
    weights = q + kv + o + mlp + head
    assert weights == 504_954_880
    # causal attention at 2048: 4*40*128 per earlier position forward,
    # (2048+1)/2 positions on average, 3x for forward and backward
    attention = 3 * 4 * 40 * 128 * (2048 + 1) / 2
    assert _count("phi3-medium-1l", 2048) == pytest.approx(6 * weights + attention, rel=1e-12)
    assert 6 * weights + attention == pytest.approx(3.0927e9, rel=1e-4)


def test_mamba2_24_layers_by_hand():
    # in_proj 2048 -> 2*4096 + 2*128 + 64 = 8512; out_proj 4096 -> 2048;
    # the head tied to the 50277 x 2048 embedding
    per_layer = 2048 * 8512 + 4096 * 2048
    weights = 24 * per_layer + 50277 * 2048
    assert weights == 722_675_712
    # SSD per token per layer, chunk 256, 64 heads of 64, state 128, 1 group:
    # C.B^T 2*128*128.5, scores.x 2*4096*128.5, states 2*4096*128,
    # carried states read back 2*4096*128
    ssd = 2 * 128 * 128.5 + 2 * 4096 * 128.5 + 4 * 4096 * 128
    total = 6 * weights + 3 * 24 * ssd
    assert _count("mamba2-1.3b-24l", 2048) == pytest.approx(total, rel=1e-12)
    assert total == pytest.approx(4.5652e9, rel=1e-4)


def test_attention_term_grows_with_the_sequence_and_ssd_does_not():
    assert _count("phi3-medium-1l", 4096) > _count("phi3-medium-1l", 2048)
    assert _count("mamba2-1.3b-24l", 4096) == _count("mamba2-1.3b-24l", 2048)
