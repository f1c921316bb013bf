"""The operation and byte counters, against hand counts at the published
widths of the benchmark's configurations."""
import pytest

from chipbench import counts, spec

V5E = {"bf16_flops": 1.97e14, "int8_ops": 3.93e14, "hbm_bytes_per_s": 8.19e11}


def test_imagebind_vision_layer():
    # S = 257 (256 patches + CLS), d = 1280, d_ff = 5120:
    # 8 S d^2 = 3,368,550,400; 4 S^2 d = 338,170,880;
    # 6 S d f = 10,105,651,200
    assert counts.layer_flops(257, 1280, 5120) == 13_812_372_480


def test_imagebind_text_layer():
    # S = 78 (77 tokens + CLS), d = 1024, d_ff = 4096
    assert counts.layer_flops(78, 1024, 4096) == (
        654_311_424 + 24_920_064 + 1_962_934_272)


def test_clip_towers():
    assert counts.layer_flops(257, 1024, 4096) == (
        2_155_872_256 + 270_536_704 + 6_467_616_768)
    assert counts.layer_flops(78, 768, 3072) == (
        368_050_176 + 18_690_048 + 1_104_150_528)


def test_frontend_and_exit_head():
    cfg = spec._load_json(spec.BENCH_DIR / "configs" /
                          "recall-imagebind.json")
    vis, txt = spec.tower(cfg, "vision"), spec.tower(cfg, "text")
    assert counts.frontend_flops(vis, cfg) == 2 * 256 * 1024 * 1280
    assert counts.frontend_flops(txt, cfg) == 0
    assert counts.exit_head_flops(vis, cfg) == 2 * 1280 * 1024


def test_ingest_item_at_exit_20():
    # the issue's 13.8 GFLOP a layer, 20 layers, plus the patch projection
    per_item = 20 * counts.layer_flops(257, 1280, 5120) + \
        2 * 256 * 1024 * 1280 + 2 * 1280 * 1024
    assert per_item == pytest.approx(2.769e11, rel=1e-3)


def test_scan_work_and_roofline():
    ops, nbytes = counts.scan_work(192, 2 ** 20, 1024)
    assert ops == 412_316_860_416
    assert nbytes == 2 ** 20 * 516 + 192 * 1024 * 4
    t, bound = counts.least_time(ops, nbytes, V5E)
    assert bound == "int8 compute"
    assert t == pytest.approx(ops / 3.93e14)
    ops, nbytes = counts.scan_work(192, 2 ** 20, 768)
    assert ops == 2 * 192 * 2 ** 20 * 768
    assert counts.least_time(ops, nbytes, V5E)[0] == pytest.approx(
        7.87e-4, rel=1e-3)


def test_bank_capacity_follows_the_store_doubling():
    assert counts.bank_capacity(2 ** 20) == 2 ** 20
    assert counts.bank_capacity(1_000_000) == 2 ** 20
    assert counts.bank_capacity(2 ** 20 + 1) == 2 ** 21
    assert counts.bank_capacity(10) == 64
