"""Operation and byte counts of the kernels and the decode step against
hand counts at both configurations' published shapes, and the sources
that the peaks and the counts carry."""
import json
import os

import pytest

from bench import common


def cfg(name):
    return common.load_json("configs", name)


def test_peaks_have_their_source():
    with open(os.path.join(common.BENCH_DIR, "peaks.json")) as f:
        table = json.load(f)
    v5e = table["devices"]["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in v5e["source"]
    for dev in table["devices"].values():
        assert dev["source"]


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        common.peaks("TPU v99")


@pytest.mark.parametrize("kernel", ["paged_decode_attention", "decode_step",
                                    "shortlist_topk"])
def test_each_count_says_what_it_counts(kernel):
    mod = common.load_reader("kernels", kernel)
    assert mod.__doc__ and len(mod.__doc__) > 100


def test_paged_decode_attention_qwen_by_hand():
    k = common.load_reader("kernels", "paged_decode_attention")
    # 12 query heads, 2 kv heads, head 128; position 99 -> 100 live keys
    flops, nbytes = k.cost(cfg("qwen2-1.5b"), 99)
    assert flops == 4 * 12 * 128 * 100 == 614_400
    assert nbytes == 2 * (2 * 2 * 128 * 100 + 2 * 12 * 128) == 108_544


def test_paged_decode_attention_granite_by_hand():
    k = common.load_reader("kernels", "paged_decode_attention")
    flops, nbytes = k.cost(cfg("granite-3-2b"), 255)   # 256 live keys
    assert flops == 4 * 32 * 64 * 256 == 2_097_152
    assert nbytes == 2 * (2 * 8 * 64 * 256 + 2 * 32 * 64) == 532_480


def test_decode_step_qwen_by_hand():
    k = common.load_reader("kernels", "decode_step")
    c = cfg("qwen2-1.5b")
    # per layer: q 1536x1536, k and v 1536x256 each, o 1536x1536,
    # gate/up/down 3 x 1536x8960; tied logits 151936x1536
    layer = 1536 * 1536 * 2 + 2 * 1536 * 256 + 3 * 1536 * 8960
    w = 28 * layer + 151936 * 1536
    assert k.weights(c) == w == 1_543_569_408
    flops, nbytes = k.cost(c, 0)
    assert flops == 2 * w + 28 * 4 * 12 * 128 * 1
    assert nbytes == 2 * (w + 28 * 2 * 2 * 128 * 1)
    # the weight floor of one step: 3.09 GB at 819 GB/s
    assert nbytes / 819e9 == pytest.approx(3.767e-3, rel=1e-3)


def test_decode_step_granite_by_hand():
    k = common.load_reader("kernels", "decode_step")
    c = cfg("granite-3-2b")
    layer = 2048 * 2048 * 2 + 2 * 2048 * 512 + 3 * 2048 * 8192
    w = 40 * layer + 49155 * 2048
    assert k.weights(c) == w == 2_533_365_760
    flops, nbytes = k.cost(c, 63)
    assert flops == 2 * w + 40 * 4 * 32 * 64 * 64
    assert nbytes == 2 * (w + 40 * 2 * 8 * 64 * 64)


def test_shortlist_topk_by_hand():
    k = common.load_reader("kernels", "shortlist_topk")
    flops, nbytes = k.cost(64, 1000)
    assert flops == 2 * 64 * 1000
    assert nbytes == 1000 * (4 * 64 + 8) + 4 * 64


@pytest.mark.parametrize("name", ["qwen2-1.5b", "granite-3-2b"])
def test_weight_shapes_match_the_counts(name):
    from bench import model
    c = cfg(name)
    shapes = model.param_shapes(c)
    n = 1
    total = 0
    for s in [shapes["embed"]] + [v for k, v in shapes["layers"].items()
                                  if k.startswith("w")]:
        n = 1
        for d in s:
            n *= d
        total += n
    assert total == common.load_reader("kernels", "decode_step").weights(c)
