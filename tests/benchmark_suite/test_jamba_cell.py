"""The cell ``jamba2_3b_chat_open``'s own yardstick files: ``flops_jamba``
against hand counts, the driver ``llm_serve_jamba`` rehearsed through
``run.py`` at a tiny size on the CPU, and the faults a hybrid model can have,
each planted under such a run: ``correct`` has to come out false."""

import dataclasses
import io
import json
import os
from contextlib import redirect_stdout

import pytest

import fixture_root

fixture_root.bench_imports()

import flops_jamba  # noqa: E402
import harness  # noqa: E402
import run as bench_run  # noqa: E402

TINY_JAMBA = {
    "name": "tiny_jamba", "source": "test fixture", "model_type": "jamba", "attn_layer_offset": 1,
    "attn_layer_period": 4, "hidden_size": 128, "intermediate_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_state": 16, "mamba_dt_rank": 8, "mamba_expand": 2, "mamba_proj_bias": False,
    "num_attention_heads": 4, "num_experts": 1, "num_experts_per_tok": 1, "num_hidden_layers": 4,
    "num_key_value_heads": 1, "rms_norm_eps": 1e-06, "tie_word_embeddings": True, "vocab_size": 512,
}
TINY_SERVE = {
    "driver": "llm_serve_jamba",
    "program": {"max_seq_len": 128, "num_slots": 4, "decode_chunk": 4, "page_size": 16, "client_threads": 8,
                "client_timeout_s": 60.0, "drain_s": 60.0, "snapshot_budget_states": 4},
    "check": {"sample_requests": 4, "pad_to": 128},
    "limits": {"widest_logit_gap": 0.05, "mean_logit_gap": 0.01},
}
# turns that are NOT multiples of the 16-token bucket: every prefill is padded
TINY_CHAT = {"kind": "open_loop_chat", "rate_per_s": 4.0, "arrivals": "poisson", "system_prompt_tokens": 32,
             "system_prompt_share": 0.75, "user_tokens": {"values": [11, 27], "weights": [0.5, 0.5]},
             "max_new_tokens": {"values": [5, 9], "weights": [0.5, 0.5]}, "temperature": 0.0}
CELL = "tiny_jamba_chat"
E2E = {"serve_latency_p95_ms", "serve_out_tokens_per_s", "setup_s"}
LAYER = {"serve_ttft_p95_ms", "serve_prefix_hit_pct", "serve_tpot_p50_ms", "gen_lateness_p95_ms",
         "serve_slot_occupancy_pct", "compiles_in_window.serve", "device_idle_pct.serve", "serve_state_hit_pct"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = fixture_root.make_root(tmp_path_factory.mktemp("bench"))
    fixture_root.add_cell(root, CELL, "tiny_jamba", TINY_JAMBA, "tiny_chat_ragged", TINY_CHAT, TINY_SERVE,
                          {"serve_latency_p95_ms", "serve_out_tokens_per_s"})
    return root


def _run(root, trace=0, seed=2**31 + 29):
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = bench_run.main(["--workload", CELL, "--seed", str(seed), "--seconds", "2", "--trace", str(trace)],
                            root=root, allow_cpu=True)
    lines = buf.getvalue().strip().splitlines()
    assert rc == 0 and len(lines) == 1
    return json.loads(lines[-1])


def _driver(root):
    return harness.load_module(os.path.join(root, "benchmark", "drivers", "llm_serve_jamba.py"))


# ---- required work, from shapes ------------------------------------------------------------------

def test_flops_jamba_against_hand_counts():
    with open(os.path.join(fixture_root.BENCH, "configs", "jamba2-3b.json")) as f:
        cfg = json.load(f)
    assert flops_jamba.layer_kinds(cfg).count("mamba") == 26 and flops_jamba.layer_kinds(cfg).index("attention") == 7
    # a Mamba layer: in_proj 2560x10240 + x_proj 5120x192 + dt_proj 160x5120 + out_proj 5120x2560
    assert flops_jamba.mamba_matmul_params(cfg) == 26214400 + 983040 + 819200 + 13107200 == 41123840
    # + conv 5120x4 + conv bias + dt bias + A_log 5120x16 + D + the norms of dt, B, C (160 + 16 + 16)
    assert flops_jamba.mamba_other_params(cfg) == 20480 + 5120 + 5120 + 81920 + 5120 + 192
    assert flops_jamba.mlp_params(cfg) == 3 * 2560 * 8192 == 62914560
    assert flops_jamba.attn_matmul_params(cfg) == 2 * 2560 * 2560 + 2 * 2560 * 128 == 13762560
    assert flops_jamba.layer_params(cfg, "mamba") == 104161472 and flops_jamba.layer_params(cfg, "attention") == 76682240
    assert flops_jamba.embedding_params(cfg) == 167772160
    assert flops_jamba.total_params(cfg) == 26 * 104161472 + 2 * 76682240 + 167772160 + 2560 == 3029337472  # 3.03 B
    assert flops_jamba.state_bytes(cfg) == 26 * (16 * 5120 * 4 + 3 * 5120 * 2) == 9318400
    assert flops_jamba.kv_bytes_per_token(cfg) == 2 * 2 * 1 * 128 * 2 == 1024
    assert flops_jamba.scan_flops_per_token(cfg) == 5120 * 16 * 7 + 5120 * 3 == 588800
    fl, by = flops_jamba.scan_call_cost(cfg, 1280)
    assert fl == 1280 * 588800 and by == 4 * (3 * 1280 * 5120 + 2 * 1280 * 16 + 16 * 5120 + 5120 + 3 * 16 * 5120)
    # a token-step: weights once + live K/V + each live slot's state read and written
    assert flops_jamba.decode_step_bytes(cfg, 0, 0) == 2 * 3029337472
    assert flops_jamba.decode_step_bytes(cfg, 1000, 10) == 2 * 3029337472 + 1000 * 1024 + 10 * 2 * 9318400
    # serving: one prompt of 4 tokens from position 0, one decoded token at context 5
    blocks = 26 * 41123840 + 2 * 13762560 + 28 * 62914560
    per_token = 2 * blocks + 26 * (588800 + 2 * 5120 * 4)
    attn = 4 * 20 * 128 * 2
    want = per_token * 4 + 2 * 167772160 + attn * (4 * 5 / 2) + per_token + 2 * 167772160 + attn * 5
    assert flops_jamba.serve_flops(cfg, [(4, 0)], [5]) == want


# ---- the driver, rehearsed -------------------------------------------------------------------------

def test_untraced_rehearsal_prints_the_contract_line(root):
    out = _run(root, 0)
    assert list(out) == ["correct", "attempted", "failed", "metrics", "device", "compared"]
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] == 8
    assert set(out["metrics"]) == E2E and all(m["value"] > 0 for m in out["metrics"].values())
    assert out["compared"]["page_leaks"] == {"value": 0.0, "limit": 0}


def test_traced_rehearsal_reports_the_state_hits_and_no_compile(root):
    out = _run(root, 1)
    assert out["correct"] is True
    assert set(out["metrics"]) == LAYER      # rooflines and MFUs need the chip's peaks: nothing on the CPU
    assert out["metrics"]["compiles_in_window.serve"]["value"] == 0   # the second warm-up pass covered the suffix buckets
    assert out["metrics"]["serve_state_hit_pct"]["value"] == 75.0     # every system-prompt request from the snapshot
    assert out["metrics"]["serve_prefix_hit_pct"]["value"] == 75.0


def test_the_real_cell_reports_both_serving_metrics_and_every_model_blind_reader():
    """``jamba2_3b_chat_open`` is a cell of both end-to-end serving metrics and of every reader that
    does not count a dense block's work (ISSUE 29's list), not of ``decode_hbm_roofline`` /
    ``serve_step_mfu`` (``flops.py`` would count 28 dense-attention layers)."""
    cell = harness.Cell(os.path.dirname(fixture_root.BENCH), "jamba2_3b_chat_open")
    assert {m["name"] for m in cell.end_to_end()} == {"serve_latency_p95_ms", "serve_out_tokens_per_s", "setup_s"}
    layer = {m["name"]: m["moves"] for m in cell.per_layer()}
    assert set(layer) == {
        "serve_ttft_p95_ms", "serve_prefix_hit_pct", "serve_tpot_p50_ms", "gen_lateness_p95_ms",
        "serve_slot_occupancy_pct", "compiles_in_window.serve", "device_idle_pct.serve", "serve_queue_wait_p95_ms",
        "serve_admit_p95_ms", "serve_entry_self_p95_ms", "serve_loop_admit_pct", "serve_loop_host_pct",
        "serve_decode_batch_mean", "serve_admit_device_pct", "ssm_serve_step_mfu", "ssm_decode_hbm_roofline",
        "selective_scan_roofline", "serve_state_hit_pct", "serve_scan_device_pct"}
    assert {n for n, moves in layer.items() if n.startswith(("selective_scan", "serve_state", "serve_scan"))
            and moves == "serve_latency_p95_ms"} == {"selective_scan_roofline", "serve_state_hit_pct",
                                                     "serve_scan_device_pct"}


def test_the_scan_reader_takes_its_shapes_from_the_programs_own_prefill_spans():
    """Tokens of each pass of the traced part: ``prompt_len - shared`` of ``serving.cb.prefill``,
    padded to the engine's 16-token bucket; spans outside the traced part or without the
    attributes are left out; a run that kept no bounds of a traced part gives nothing."""
    import program_spans

    reader = harness.load_module(os.path.join(fixture_root.BENCH, "metrics", "selective_scan_roofline.py"))
    spans = [("serving.cb.prefill", 9.0, {"prompt_len": 300, "shared": 256}),    # before the traced part
             ("serving.cb.prefill", 10.5, {"prompt_len": 300, "shared": 256}),   # 44 -> 48
             ("serving.cb.prefill", 11.0, {"prompt_len": 1280, "shared": 0}),
             ("serving.cb.prefill", 11.5, {"request_id": "r"}),
             ("serving.cb.chunk", 11.6, {"slots": 3}),
             ("serving.cb.prefill", 15.5, {"prompt_len": 64, "shared": 0})]      # after it
    snap = {"epoch_perf_ns": 0, "spans": [{"name": n, "t0_ns": int(t * 1e9), "dur_ns": 1000, "attrs": a}
                                          for n, t, a in spans]}
    run = {program_spans.SNAPSHOT_KEY: snap, "window": {"t_start": 0.0, "t_close": 40.0, "trace_t0": 10.0, "trace_t1": 15.0}}
    assert reader.pass_tokens(run) == [48, 1280]
    run["window"] = {"t_start": 0.0, "t_close": 40.0}
    assert reader.pass_tokens(run) == []


def test_dense_cell_reports_none_of_the_new_metrics(root):
    """The new readers say nothing in a cell whose model has no recurrent layer
    (fixture_root lists tiny_chat under every serving metric)."""
    cell = harness.Cell(root, "tiny_chat")
    new = {"ssm_serve_step_mfu", "ssm_decode_hbm_roofline", "selective_scan_roofline", "serve_state_hit_pct",
           "serve_scan_device_pct"}
    assert new <= {m["name"] for m in cell.per_layer()}
    run = {"ctx": type("C", (), {"peaks": None, "config": cell.config})(), "window": {"ok": 1}, "trace": None}
    assert all(cell.metric_reader(name)(run) is None for name in new)


# ---- planted faults: correct must come out false ---------------------------------------------------

def _with_config(monkeypatch, drv, **changes):
    real = drv.model_config
    monkeypatch.setattr(drv, "model_config", lambda ctx: dataclasses.replace(real(ctx), **changes))


def _fresh_programs(monkeypatch):
    from fedml_tpu.train.llm import generation

    monkeypatch.setattr(generation, "_COMPILED", {})


def test_a_zeroed_snapshot_is_not_correct(root, monkeypatch):
    import jax

    from fedml_tpu.serving import continuous_batching as cb

    real = cb.snapshot_of
    monkeypatch.setattr(cb, "snapshot_of", lambda row: jax.tree.map(lambda x: x * 0, real(row)))
    out = _run(root)
    assert out["failed"] == 0 and out["correct"] is False
    assert out["compared"]["widest_logit_gap"]["value"] > out["compared"]["widest_logit_gap"]["limit"]


def test_padded_positions_that_advance_the_state_are_not_correct(root, monkeypatch):
    import jax.numpy as jnp

    from fedml_tpu.models import mamba
    from fedml_tpu.ops import selective_scan as ss

    def ignores_the_true_length(u, dt, a_t, b, c, d_skip, h0, length, snap):
        return ss.selective_scan_reference(u, dt, a_t, b, c, d_skip, h0, jnp.full_like(length, u.shape[1]), snap)

    _fresh_programs(monkeypatch)
    monkeypatch.setattr(mamba, "_selective_scan_impl", lambda *a: ignores_the_true_length)
    out = _run(root)
    _fresh_programs(monkeypatch)
    assert out["failed"] == 0 and out["correct"] is False


def test_rotary_left_on_is_not_correct(root, monkeypatch):
    _fresh_programs(monkeypatch)
    _with_config(monkeypatch, _driver(root), use_rope=True)
    out = _run(root)
    assert out["failed"] == 0 and out["correct"] is False


def test_a_head_that_is_not_tied_is_not_correct(root, monkeypatch):
    """The program given a head of its own (a leaf the reference never reads)."""
    _fresh_programs(monkeypatch)
    _with_config(monkeypatch, _driver(root), tie_embeddings=False)
    out = _run(root)
    assert out["failed"] == 0 and out["correct"] is False
