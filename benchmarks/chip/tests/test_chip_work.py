"""Work counts against hand arithmetic at both configurations' widths."""
import json

import pytest

from tiny import BENCH
import work


def model(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())["model"]


def test_kv_bytes_per_token():
    assert work.kv_bytes_per_token(model("dsr1d-qwen-1.5b")) == 28_672
    assert work.kv_bytes_per_token(model("gpt2-xl")) == 307_200


def test_matmul_params():
    # 28 x (2*1536*1536 + 2*1536*256 + 3*1536*8960) + 151936*1536
    assert work.matmul_params(model("dsr1d-qwen-1.5b")) == 1_543_569_408
    # 48 x (4*1600*1600 + 2*1600*6400) + 50257*1600
    assert work.matmul_params(model("gpt2-xl")) == 1_554_971_200


def test_model_flops():
    m = model("dsr1d-qwen-1.5b")
    # one token at 1000 rows of context: 2 x params + 4 x 28 x 12 x 128 x 1000
    assert work.model_flops(m, 1, 1000) == 2 * 1_543_569_408 + 172_032_000


def test_paged_attn_work():
    m = model("gpt2-xl")
    flops, nbytes = work.paged_attn_work(m, slot_steps=2, context_rows=300)
    assert flops == 4 * 48 * 25 * 64 * 300
    # K and V rows: 307,200 B each; q and output: 2 x 25 x 64 x 2 B a layer
    assert nbytes == 307_200 * 300 + 48 * 2 * 25 * 64 * 2 * 2


@pytest.mark.parametrize("prompt,before,gained,rows", [
    (100, 1, 3, 101 + 102 + 103),      # first decode steps after the prefill
    (64, 10, 1, 74),
    (64, 10, 0, 0),
])
def test_decode_rows(prompt, before, gained, rows):
    assert work.decode_rows(prompt, before, gained) == rows


def test_roofline_share_takes_the_larger_bound():
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # 819 MB at 819 GB/s is 1 ms; 1 GFLOP is far less: 1 ms of 2 ms
    assert work.roofline_share(1e9, 819e6, 2e-3, peak) == pytest.approx(50.0)
    assert work.roofline_share(197e9, 0, 1e-3, peak) == pytest.approx(100.0)


def test_stage2_work():
    assert work.stage2_work(90_000, 336, 64) == (90_000 * 336 * 64,
                                                  90_000 * 336 * 8)
