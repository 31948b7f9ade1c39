"""``benchmark/tools/handoff_cost.py`` rehearsed on the CPU at a tiny size: the
admit and gather programs alone, at a dense cell's and a two-group cell's shapes,
found by name in a trace (the CPU client's threads stand in for a device: no
number here is a device's), the owned pages checked against the row."""

import json
import os

import pytest

import fixture_root

fixture_root.bench_imports()

import harness  # noqa: E402
from test_trinity_cell import TINY_CHAT, TINY_SERVE, TINY_TRINITY  # noqa: E402

CELL = "tiny_trinity_chat"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = fixture_root.make_root(tmp_path_factory.mktemp("bench"))
    fixture_root.add_cell(root, CELL, "tiny_trinity", TINY_TRINITY, "tiny_chat_long", TINY_CHAT, TINY_SERVE,
                          {"serve_latency_p95_ms", "serve_out_tokens_per_s"})
    return root


def test_the_tool_prices_both_programs_and_checks_the_pages(root, capsys):
    tool = harness.load_module(os.path.join(root, "benchmark", "tools", "handoff_cost.py"))
    rc = tool.main(["--workloads", f"tiny_chat,{CELL}", "--turns", "40,90", "--seed", "77"], root=root, allow_cpu=True)
    assert rc == 0
    with open(os.path.join(root, "chiprun_out", "handoff_cost.jsonl")) as f:
        rows = [json.loads(line) for line in f]
    # the cell's own shortest and longest turn, and those of --turns its row can hold behind the system prompt
    assert [(r["workload"], r["turn"]) for r in rows] == [
        ("tiny_chat", 16), ("tiny_chat", 32), ("tiny_chat", 40), ("tiny_chat", 90),
        (CELL, 11), (CELL, 40), (CELL, 60), (CELL, 90)]
    dense, two = rows[3], rows[7]
    # 2 layers x (k, v) x 8 blocks of 16; the prompt's 8 blocks less the 2 shared
    assert dense["blocks_row"] == 32 and dense["blocks_owned"] == 4 * 6 and dense["prompt"] == 122
    # 5 layers x (k, v) x 32 blocks of 4; 106 tokens: 27 blocks less 4 shared in the full layer, the window's 3 in four
    assert two["blocks_row"] == 320 and two["blocks_owned"] == 2 * 23 + 8 * 3
    assert all(r["ranged"] for r in rows)
    table = capsys.readouterr().out
    assert "| tiny_chat | . | 122 | 24 / 32 |" in table
