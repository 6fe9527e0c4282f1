"""`attn_live_block_share.backlog` reads the engine's paged-attention block
counters: 100 x live / total on a synthetic record, nothing where the
program lacks a counter (a program before the block walk) or ran no
block, and a share between 0 and 100 in a served tiny cell."""
import json
import math
from types import SimpleNamespace

import pytest

import tiny

import spec  # noqa: E402  (tiny puts the harness on the path)

NAME = "attn_live_block_share.backlog"
ENTRY = next(m for m in json.loads((tiny.REPO / "BENCHMARK.json").read_text())
             ["per_layer"] if m["name"] == NAME)


def _read(stats):
    mod = spec.load("metric_attn_live_block_share",
                    tiny.BENCH / "metrics" / f"{NAME}.py")
    return mod.read(SimpleNamespace(counts={"stats": stats}))


def test_entry_is_a_program_counter_of_the_backlog_cells():
    assert ENTRY["source"] == "program_counter"
    assert ENTRY["layer"] == "paged attention"
    assert ENTRY["moves"] == "tokens_per_s" and ENTRY["better"] == "lower"
    assert ENTRY["workloads"] == ["dsr1d-reasoning-backlog",
                                  "gpt2xl-chat-backlog"]


@pytest.mark.parametrize("stats,want", [
    ({"attn_blocks_live": 384, "attn_blocks_total": 1280, "chunks": 3}, 30.0),
    ({"attn_blocks_live": 0, "attn_blocks_total": 256}, 0.0),
    ({"attn_blocks_live": 256, "attn_blocks_total": 256}, 100.0),
    ({"attn_blocks_total": 256, "chunks": 3}, None),    # parent's program
    ({"attn_blocks_live": 3}, None),
    ({"attn_blocks_live": 0, "attn_blocks_total": 0}, None),
    ({}, None),
])
def test_reads_a_synthetic_record(stats, want):
    got = _read(stats)
    assert got == want if want is None else got == pytest.approx(want)


def test_served_cell_reads_a_share(tmp_path, monkeypatch):
    root = tiny.checkout(tmp_path, extra_metrics=[ENTRY])
    out = tiny.run_cell(root, "tiny-backlog", seed=2**31 + 11, trace=1,
                        monkeypatch=monkeypatch)
    assert out["correct"] is True
    v = out["metrics"][NAME]["value"]
    assert math.isfinite(v) and 0.0 < v <= 100.0
