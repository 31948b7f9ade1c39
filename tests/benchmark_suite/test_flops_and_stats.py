"""The benchmark's own arithmetic against hand counts: FLOPs and bytes for both
configurations, percentiles and rates over a window that holds a stall."""

import math
import os

import pytest

import fixture_root

fixture_root.bench_imports()

import flops  # noqa: E402
import harness  # noqa: E402
import stats  # noqa: E402

MISTRAL = harness.load_json(os.path.join(fixture_root.BENCH, "configs", "mistral-7b-v0.3.json"))
INTERNLM = harness.load_json(os.path.join(fixture_root.BENCH, "configs", "internlm2-7b.json"))

# one block at these widths, by hand: q 4096x4096, k and v 4096x1024 each,
# o 4096x4096, gate/up/down 3 x 4096x14336
BLOCK = 4096 * 4096 * 2 + 4096 * 1024 * 2 + 3 * 4096 * 14336


@pytest.mark.parametrize("cfg,layers,vocab", [(MISTRAL, 4, 32768), (INTERNLM, 8, 92544)])
def test_matmul_params_by_hand(cfg, layers, vocab):
    assert BLOCK == 218_103_808
    assert flops.layer_matmul_params(cfg) == BLOCK
    assert flops.head_params(cfg) == 4096 * vocab
    assert flops.matmul_params(cfg) == layers * BLOCK + 4096 * vocab
    assert flops.total_params(cfg) == layers * BLOCK + 2 * 4096 * vocab + (2 * layers + 1) * 4096


def test_configs_state_published_widths_and_their_cut():
    for cfg in (MISTRAL, INTERNLM):
        assert (cfg["hidden_size"], cfg["intermediate_size"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"], cfg["head_dim"]) == (4096, 14336, 32, 8, 128)
        assert cfg["reduced"] == ["num_hidden_layers"] and cfg["published"]["num_hidden_layers"] == 32
    assert MISTRAL["sliding_window"] is None and MISTRAL["vocab_size"] == 32768
    assert INTERNLM["vocab_size"] == 92544


def test_lora_params_by_hand():
    # r=64 on q (4096->4096), k, v (4096->1024), o (4096->4096), 4 layers
    per_layer = 64 * ((4096 + 4096) + 2 * (4096 + 1024) + (4096 + 4096))
    assert flops.lora_params(MISTRAL, 64) == 4 * per_layer == 6_815_744


def test_train_step_flops_by_hand():
    n = 4 * BLOCK + 4096 * 32768
    tokens = 4 * 2048
    unit = 2 * (2048 * 2048 / 2) * 128 * 32          # one causal [T,hd]x[hd,T] over 32 heads
    want = tokens * (4 * n + 6 * 6_815_744) + 4 * 4 * 6 * unit
    got = flops.train_step_flops(MISTRAL, 64, 4, 2048)
    assert got == pytest.approx(want, rel=1e-12)
    assert 4.2e9 < got / tokens < 4.35e9               # the issue's "4.2 GFLOP a token"
    # never the 6N of a full fine-tune, and never max_seq_len in place of the batch's length
    assert got < tokens * 6 * n


@pytest.mark.parametrize("kind,units", [("fwd", 2), ("dq", 3), ("dkv", 4)])
def test_flash_call_cost_by_hand(kind, units):
    fl, by = flops.flash_call_cost(MISTRAL, kind, 4, 2048)
    assert fl == pytest.approx(4 * units * 2 * (2048 * 2048 / 2) * 128 * 32)
    q, kv = 4 * 2048 * 32 * 128 * 2, 4 * 2048 * 8 * 128 * 2
    assert by == {"fwd": 2 * q + 2 * kv, "dq": 4 * q + 2 * kv, "dkv": 3 * q + 4 * kv}[kind]
    assert fl / by > 197e12 / 819e9                      # compute bounds it on a v5e


def test_decode_bytes_and_kv_token_by_hand():
    assert flops.kv_bytes_per_token(INTERNLM) == 8 * 2 * 8 * 128 * 2 == 32768
    n = 8 * BLOCK + 4096 * 92544
    assert flops.decode_step_bytes(INTERNLM, 1000) == 2 * n + 1000 * 32768
    assert 2 * n == pytest.approx(4.25e9, rel=0.01)


def test_serve_flops_by_hand():
    n_blocks, n_head = 8 * BLOCK, 4096 * 92544
    attn = 4 * 32 * 128 * 8
    got = flops.serve_flops(INTERNLM, [(64, 256)], [320, 321])
    want = (2 * n_blocks * 64 + 2 * n_head + attn * (64 * 256 + 64 * 65 / 2)
            + 2 * (2 * (n_blocks + n_head)) + attn * (320 + 321))
    assert got == pytest.approx(want, rel=1e-12)


def test_peaks_table_is_keyed_by_device_kind():
    cell = harness.Cell(fixture_root.REPO, "mistral7b_lora_pack2k")
    assert cell.peaks("TPU v5 lite") == {"bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12,
                                         "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
    with pytest.raises(harness.HarnessError):
        cell.peaks("TPU v9000")


@pytest.mark.parametrize("q,want", [(50, 5.0), (95, 10.0), (90, 9.0), (100, 10.0), (10, 1.0)])
def test_percentile_is_nearest_rank(q, want):
    assert stats.percentile([float(i) for i in range(10, 0, -1)], q) == want


def test_percentile_refuses_nothing():
    with pytest.raises(ValueError):
        stats.percentile([], 95)
    with pytest.raises(ValueError):
        stats.percentile([1.0], 0)


def test_latency_tail_over_a_window_with_a_stall_and_a_failure():
    # 100 requests, one every 10 ms, each takes 50 ms; the server stalls for 1 s
    # at t = 0.5: the 100 requests due in the stall each wait for its end.
    lat = []
    for i in range(200):
        due = 0.01 * i
        start = max(due, 1.5) if 0.5 <= due < 1.5 else due
        lat.append(start + 0.05 - due)
    assert stats.latency_percentile_ms(lat, 50) == pytest.approx(50.0)
    assert stats.latency_percentile_ms(lat, 95) == pytest.approx(950.0)  # rank 190 of 200: the 90th of the 100 stalled, 60 ms + 89 x 10 ms
    # a failed request counts as the worst seen, so the tail can only rise
    worst = max(lat)
    assert stats.latency_percentile_ms(lat + [None] * 30, 95) == pytest.approx(1e3 * worst)
    with pytest.raises(ValueError):
        stats.latency_percentile_ms([None, None], 95)


def test_rate_is_over_all_the_time_of_the_window():
    # 10 steps of 1,000 tokens, a 2 s stall in the middle: the stall counts
    assert stats.rate_per_s(10_000, 100.0, 100.0 + 10 * 0.5 + 2.0) == pytest.approx(10_000 / 7.0)
    with pytest.raises(ValueError):
        stats.rate_per_s(1, 5.0, 5.0)


def test_spread_is_the_interquartile_distance_over_the_median():
    import statistics
    vals = [100.0, 101.0, 99.0, 100.5, 98.0, 102.0]
    q = statistics.quantiles(vals, n=4)
    assert stats.spread(vals) == pytest.approx((q[2] - q[0]) / statistics.median(vals))
    assert math.isfinite(stats.spread(vals))
