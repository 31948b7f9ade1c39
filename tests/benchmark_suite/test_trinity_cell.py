"""The cell ``trinity_mini_longmix_over``'s own yardstick files: the driver
``llm_serve_trinity`` rehearsed through ``run.py`` at a tiny size on the CPU (a
model with window and full attention layers side by side, a head size of its
own, QK norms, a gated attention output, a dense layer before routed ones with a
selection bias), the new readers, the counts of ``flops_trinity``, the files'
own consistency, and the faults such a model can have, each planted under such
a run: ``correct`` has to come out false by the cell's own comparison."""

import dataclasses
import io
import json
import os
from contextlib import redirect_stdout

import numpy as np
import pytest

import fixture_root

fixture_root.bench_imports()

import flops_trinity  # noqa: E402
import harness  # noqa: E402
import run as bench_run  # noqa: E402
import traffic  # noqa: E402

TINY_TRINITY = {
    "name": "tiny_trinity", "source": "test fixture", "model_type": "afmoe", "head_dim": 32, "hidden_act": "silu",
    "hidden_size": 64, "intermediate_size": 128,
    "layer_types": ["sliding_attention", "sliding_attention", "full_attention", "sliding_attention",
                    "sliding_attention"],
    "moe_intermediate_size": 32, "mup_enabled": True, "n_group": 1, "num_attention_heads": 4, "num_dense_layers": 1,
    "num_experts": 8, "num_experts_per_tok": 2, "num_hidden_layers": 5, "num_key_value_heads": 2,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000, "route_norm": True,
    "route_scale": 2.826, "score_func": "sigmoid", "sliding_window": 8, "tie_word_embeddings": False,
    "topk_group": 1, "vocab_size": 512, "router_width": 8, "expert_rank": 0,
}
TINY_SERVE = {
    "driver": "llm_serve_trinity",
    "program": {"max_seq_len": 128, "num_slots": 4, "decode_chunk": 4, "page_size": 4, "client_threads": 8,
                "client_timeout_s": 120.0, "drain_s": 120.0, "num_pages": 129},
    "check": {"sample_requests": 4, "pad_to": [128]},
    "limits": {"widest_logit_gap": 0.5, "mean_logit_gap": 0.03},
}
# turns that are NOT multiples of the 16-token bucket: every prefill is padded; every prompt is longer than
# the window of 8, and the longest (76 + 9) is over ten windows
TINY_CHAT = {"kind": "open_loop_chat", "rate_per_s": 4.0, "arrivals": "poisson", "system_prompt_tokens": 16,
             "system_prompt_share": 0.75, "user_tokens": {"values": [11, 27, 60], "weights": [0.4, 0.3, 0.3]},
             "max_new_tokens": {"values": [5, 9], "weights": [0.5, 0.5]}, "temperature": 0.0}
CELL = "tiny_trinity_chat"
REAL = "trinity_mini_longmix_over"
E2E = {"serve_latency_p95_ms", "serve_out_tokens_per_s", "setup_s"}
NEW = {"trinity_serve_step_mfu", "trinity_decode_hbm_roofline", "paged_window_attn_roofline",
       "flash_window_fwd_roofline", "serve_attn_device_pct", "serve_window_pages_pct"}
SHARED = {"serve_expert_device_pct", "moe_grouped_matmul_roofline", "serve_expert_load_imbalance"}
GENERIC = {"serve_slot_occupancy_pct", "compiles_in_window.serve", "device_idle_pct.serve", "serve_loop_admit_pct",
           "serve_loop_host_pct", "serve_decode_batch_mean", "serve_admit_device_pct"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = fixture_root.make_root(tmp_path_factory.mktemp("bench"))
    fixture_root.add_cell(root, CELL, "tiny_trinity", TINY_TRINITY, "tiny_chat_long", TINY_CHAT, TINY_SERVE,
                          {"serve_latency_p95_ms", "serve_out_tokens_per_s"})
    return root


def _run(root, trace=0, seed=2**31 + 35):
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = bench_run.main(["--workload", CELL, "--seed", str(seed), "--seconds", "2", "--trace", str(trace)],
                            root=root, allow_cpu=True)
    lines = buf.getvalue().strip().splitlines()
    assert rc == 0 and len(lines) == 1
    return json.loads(lines[-1])


def _driver(root):
    return harness.load_module(os.path.join(root, "benchmark", "drivers", "llm_serve_trinity.py"))


# ---- the driver, rehearsed -------------------------------------------------------------------------

def test_untraced_rehearsal_prints_the_contract_line(root):
    out = _run(root, 0)
    assert list(out) == ["correct", "attempted", "failed", "metrics", "device", "compared"]
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] == 8
    assert set(out["metrics"]) == E2E and all(m["value"] > 0 for m in out["metrics"].values())
    assert out["compared"]["page_leaks"] == {"value": 0.0, "limit": 0}
    assert out["compared"]["sample_over_4_windows_missing"] == {"value": 0.0, "limit": 0}


def test_traced_rehearsal_reports_the_window_group_and_no_compile(root):
    out = _run(root, 1)
    assert out["correct"] is True
    # rooflines, MFUs and device shares need the chip's peaks: of the new readers only the counters' on the CPU
    assert (NEW | SHARED) & set(out["metrics"]) == {"serve_window_pages_pct", "serve_expert_load_imbalance"}
    # prompts of 27-76 tokens behind a window of 8: the window group holds a fraction of what no horizon would
    assert 0 < out["metrics"]["serve_window_pages_pct"]["value"] < 70
    assert out["metrics"]["compiles_in_window.serve"]["value"] == 0


def test_the_real_cell_holds_the_issues_parameters_to_the_letter():
    cell = harness.Cell(os.path.dirname(fixture_root.BENCH), REAL)
    assert {m["name"] for m in cell.end_to_end()} == E2E - {"serve_latency_p95_ms"}
    layer = {m["name"]: m["moves"] for m in cell.per_layer()}
    assert set(layer) == GENERIC | SHARED | NEW and set(layer.values()) == {"serve_out_tokens_per_s"}
    assert cell.chips == 1 and cell.entry["traffic"] == "chat_longmix" and cell.workload["driver"] == "llm_serve_trinity"
    tr, p = cell.traffic, cell.workload["program"]
    assert (tr["kind"], tr["arrivals"], tr["temperature"], tr["close_with_longest"]) == \
        ("open_loop_chat", "poisson", 0.0, True)
    assert (tr["system_prompt_tokens"], tr["system_prompt_share"]) == (256, 0.8)
    assert tr["user_tokens"] == {"values": [512, 2048, 8192, 16384], "weights": [0.4, 0.3, 0.2, 0.1]}
    assert tr["max_new_tokens"] == {"values": [32, 64, 128, 256], "weights": [0.3, 0.35, 0.25, 0.1]}
    assert (p["max_seq_len"], p["num_slots"], p["decode_chunk"]) == (16896, 64, 8)
    assert (p["num_pages"] - 1) * p["page_size"] >= 32768 * 16          # the full group: at least 524 k tokens
    assert p["max_seq_len"] % p["page_size"] == 0
    assert max(cell.workload["check"]["pad_to"]) == p["max_seq_len"]     # the longest request is compared whole
    assert len(cell.entry["why"]) <= 200 and "knee" in cell.entry["why"]
    mean_prompt = sum(v * w for v, w in zip(tr["user_tokens"]["values"], tr["user_tokens"]["weights"])) + 0.8 * 256
    assert 4200 < mean_prompt < 4400


def test_the_configuration_file_keeps_every_published_width_and_cuts_the_depth_alone():
    with open(os.path.join(fixture_root.BENCH, "configs", "trinity-mini.json")) as f:
        c = json.load(f)
    rows = [json.loads(line) for line in open("/opt/skills/guides/model-configs/architectures.jsonl")] \
        if os.path.exists("/opt/skills/guides/model-configs/architectures.jsonl") else []
    row = next((r for r in rows if r["name"] == "Trinity-Mini"), None)
    published = {"hidden_size": 2048, "num_attention_heads": 32, "num_key_value_heads": 4, "head_dim": 128,
                 "moe_intermediate_size": 1024, "num_experts": 128, "num_experts_per_tok": 8, "num_shared_experts": 1,
                 "intermediate_size": 6144, "sliding_window": 2048, "vocab_size": 200192, "route_scale": 2.826,
                 "route_norm": True, "score_func": "sigmoid", "mup_enabled": True, "rope_theta": 10000,
                 "rms_norm_eps": 1e-05, "n_group": 1, "topk_group": 1, "tie_word_embeddings": False,
                 "model_type": "afmoe", "max_position_embeddings": 131072}
    assert {k: c[k] for k in published} == published
    assert c["reduced"] == ["num_hidden_layers", "num_dense_layers", "layer_types"]
    assert {k: c[k] for k in c["reduced"]} == {
        "num_hidden_layers": 5, "num_dense_layers": 1,
        "layer_types": ["sliding_attention", "sliding_attention", "full_attention", "sliding_attention",
                        "sliding_attention"]}
    assert c["published"]["num_hidden_layers"] == 32 and c["published"]["num_dense_layers"] == 2
    assert c["layer_types"] == c["published"]["layer_types"][1:6]          # published layers 1-5
    assert set(c["cut"]) == set(c["reduced"]) and c["assumed"] and "4.24 B" in c["stands_for"]
    assert (c["router_width"], c["expert_rank"]) == (128, 0)
    if row is not None:  # the catalog's own numbers, every key but the three cut
        assert c["source"] == row["source_url"]
        assert {k: c[k] for k in row["config"] if k not in c["reduced"]} == \
            {k: v for k, v in row["config"].items() if k not in c["reduced"]}
        assert {k: c["published"][k] for k in c["reduced"]} == {k: row["config"][k] for k in c["reduced"]}
    # the cut's arithmetic: 4.24 B parameters, 8.48 GB in bfloat16
    assert flops_trinity.attn_params(c) == 27262976 and flops_trinity.expert_params(c) == 6291456
    assert flops_trinity.dense_layer_params(c) == 65011712 and flops_trinity.expert_layer_params(c) == 839122944
    assert abs(flops_trinity.total_params(c) / 4.2415e9 - 1) < 1e-3


def test_the_traffic_multiset_is_the_same_for_every_seed():
    cell = harness.Cell(os.path.dirname(fixture_root.BENCH), REAL)
    seen = []
    for seed in (0, 2**31 + 35, 77):
        reqs = traffic.open_loop_requests(cell.traffic, seed, 4.0, 1000)["requests"]
        seen.append(sorted((r["system"], len(r["prompt"]), r["max_new_tokens"]) for r in reqs))
    assert seen[0] == seen[1] == seen[2] and len(seen[0]) == round(4.0 * cell.traffic["rate_per_s"])
    assert max(n for _, n, _ in seen[0]) <= 16384 + 256


# ---- flops_trinity against brute-force counts ------------------------------------------------------

@pytest.mark.parametrize("first,n", [(0, 5), (0, 8), (0, 20), (3, 4), (3, 30), (8, 1), (40, 17), (7, 2)])
def test_keys_seen_is_the_brute_force_count(first, n):
    cfg = dict(TINY_TRINITY)
    full = sum(t + 1 for t in range(first, first + n))
    window = sum(min(t + 1, 8) for t in range(first, first + n))
    assert flops_trinity.keys_seen(cfg, first, n) == (full, window)
    # 4 window layers and 1 full one, 4 heads x 32 columns, a multiply-add for the score and one for the value
    assert flops_trinity.attn_flops(cfg, first, n) == 4 * 4 * 32 * (full + 4 * window)


def test_the_tree_of_the_program_holds_what_flops_trinity_counts(root):
    cell = harness.Cell(root, CELL)
    drv = _driver(root)
    ctx = type("C", (), {"config": cell.config, "workload": cell.workload})()
    shapes = drv.param_shapes(drv.model_config(ctx))
    size = lambda pick: sum(int(np.prod(s)) for p, s in shapes.items() if pick(p))  # noqa: E731
    small = lambda p: p.endswith("/scale") or p.endswith("router_bias")  # noqa: E731
    assert size(lambda p: not small(p)) == flops_trinity.matmul_params(cell.config)
    assert size(small) == flops_trinity.small_params(cell.config)
    assert shapes["layer_1/attn/q_proj/kernel"] == (64, 4 * 32) and shapes["layer_1/attn/g_proj/kernel"] == (64, 128)
    assert shapes["layer_1/attn/q_norm/scale"] == (32,) and shapes["layer_1/moe/router_bias"] == (8,)
    assert "layer_0/mlp/gate_proj/kernel" in shapes and "layer_0/moe/router" not in shapes
    # a token-step's bytes: the weights outside the experts once, an expert a hit, 2 x kv heads x head_dim x 2 B a key
    c = cell.config
    assert flops_trinity.kv_bytes_per_token_layer(c) == 2 * 2 * 32 * 2
    assert flops_trinity.decode_step_bytes(c, 100, 30, 5) == (
        2 * flops_trinity.non_expert_read_params(c) + 5 * 2 * 3 * 64 * 32 + 256 * (1 * 100 + 4 * 30))


def test_the_new_readers_say_nothing_for_a_dense_cell_and_compute_from_what_the_program_counted(root):
    cell = harness.Cell(root, "tiny_chat")
    assert NEW <= {m["name"] for m in cell.per_layer()}  # fixture_root lists tiny_chat under every serving metric
    run = {"ctx": type("C", (), {"peaks": None, "config": cell.config})(), "window": {"ok": 1}, "trace": None}
    assert all(cell.metric_reader(name)(run) is None for name in NEW)
    import program_spans

    with open(os.path.join(fixture_root.BENCH, "configs", "trinity-mini.json")) as f:
        cfg = json.load(f)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    chunk = {"slots": 40, "kv_tokens_full": 1_400_000, "kv_tokens_window": 500_000, "experts_hit": 2400,
             "local_picks": 10240}
    spans = [("serving.cb.chunk", 10.5, chunk),
             ("serving.cb.prefill", 11.0, {"prompt_len": 16640, "shared": 256, "local_picks": 2560, "experts_hit": 64}),
             ("serving.cb.chunk", 20.5, chunk)]  # after the traced part
    snap = {"epoch_perf_ns": 0, "spans": [{"name": n, "t0_ns": int(t * 1e9), "dur_ns": 1000, "attrs": a}
                                          for n, t, a in spans]}
    window = {"t_start": 0.0, "t_close": 40.0, "trace_t0": 10.0, "trace_t1": 15.0, "decode_chunk": 8,
              "kv_tokens_live": [200000], "ok": 10, "seconds": 40.0, "flops": 1e15,
              "window_pages_held": [600, 800], "window_pages_unbounded": [3000, 4000]}
    trace = {"op_seconds": {"paged_attention[mosaic:dq]": 0.02, "flash_attention_rows[mosaic:dq]": 0.2,
                            "paged_latent_attention[mosaic:dq]": 7.0, "fusion.1": 1.0}, "busy_s": 2.0, "chunks": 10}
    ctx = type("C", (), {"peaks": peaks, "config": cfg, "cell": type("K", (), {"chips": 1})()})()
    run = {"ctx": ctx, "window": window, "trace": trace, program_spans.SNAPSHOT_KEY: snap}
    read = lambda name: harness.load_module(os.path.join(fixture_root.BENCH, "metrics", name + ".py")).read(run)  # noqa: E731
    assert read("serve_window_pages_pct") == pytest.approx(20.0)
    assert read("serve_attn_device_pct") == pytest.approx(11.0)           # the latent kernel is another's
    assert read("trinity_serve_step_mfu") == pytest.approx(100 * 1e15 / (40 * 197e12))
    # the one chunk launched in the traced part: bytes bound it (128 FLOPs a byte of K and V), 1 full + 4 window layers
    by = lambda keys: (keys * 2048 + 40 * 8 * 2 * 4096 * 2) / 819e9  # noqa: E731
    assert read("paged_window_attn_roofline") == pytest.approx(100 * (by(1_400_000) + 4 * by(500_000)) / 0.02)
    # the one pass: compute bounds both kinds; the window layers count min(t + 1, 2048) keys a query
    full, win = flops_trinity.keys_seen(cfg, 256, 16384)
    assert win == sum(min(t + 1, 2048) for t in range(256, 16640)) and full == sum(range(257, 16641))
    least = 4 * 32 * 128 * (full + 4 * win) / 197e12
    assert read("flash_window_fwd_roofline") == pytest.approx(100 * least / 0.2)
    mod = harness.load_module(os.path.join(fixture_root.BENCH, "metrics", "trinity_decode_hbm_roofline.py"))
    assert mod.per_step(run) == (1_400_000 / 8, 500_000 / 8, 2400 / 8)  # the ONE chunk of the traced part


# ---- planted faults: correct must come out false ---------------------------------------------------

def _fresh_programs(monkeypatch):
    from fedml_tpu.train.llm import generation

    monkeypatch.setattr(generation, "_COMPILED", {})


def _served_with(monkeypatch, drv, **changes):
    """The program given another config than the one the weights and the reference were made for."""
    real = drv.build_predictor
    monkeypatch.setattr(drv, "build_predictor",
                        lambda ctx, params, cfg: real(ctx, params, dataclasses.replace(cfg, **changes)))


def _not_correct(root, monkeypatch, plant):
    _fresh_programs(monkeypatch)
    plant()
    out = _run(root)
    _fresh_programs(monkeypatch)
    assert out["failed"] == 0 and out["correct"] is False
    by = {k: v for k, v in out["compared"].items() if v["limit"] is not None and v["value"] > v["limit"]}
    assert set(by) & {"widest_logit_gap", "mean_logit_gap"}, out["compared"]


@pytest.mark.parametrize("changes", [
    {"sliding_window": 128},           # the window layers run unwindowed (the window is the whole row)
    {"use_rope": True},                # the full layer run with rotary positions
    {"qk_norm": False},                # the norms on q and k left out
    {"embed_scale": 1.0},              # the embedding not scaled
    {"moe_routed_scaling": 1.0},       # route_scale left out
], ids=lambda c: next(iter(c)))
def test_a_config_fault_is_not_correct(root, monkeypatch, changes):
    _not_correct(root, monkeypatch, lambda: _served_with(monkeypatch, _driver(root), **changes))


def test_a_bias_that_enters_the_gates_is_not_correct(root, monkeypatch):
    import jax

    from fedml_tpu.models import moe

    def biased_gates(logits, top_k, scaling, norm_topk, select_bias=None):
        scores = jax.nn.sigmoid(logits.astype("float32")) + select_bias * 30.0
        top, experts = jax.lax.top_k(scores, top_k)
        return experts.astype("int32"), top / (top.sum(-1, keepdims=True) + 1e-20) * scaling

    _not_correct(root, monkeypatch, lambda: monkeypatch.setattr(moe, "route", biased_gates))


def test_a_window_table_that_keeps_no_horizon_is_still_correct_and_one_that_loses_pages_is_not(root, monkeypatch):
    """The pages a request's horizon has passed are never read: releasing them is invisible to ``correct``.
    Releasing one page too many (a horizon one page ahead) is not."""
    from fedml_tpu.serving import continuous_batching as cb

    real = cb.PagedContinuousBatchingEngine._slide_windows

    def eager(self, active):
        self._window -= self._ps          # the host believes the window a page shorter than the model's
        try:
            real(self, active)
        finally:
            self._window += self._ps

    _not_correct(root, monkeypatch, lambda: monkeypatch.setattr(cb.PagedContinuousBatchingEngine, "_slide_windows", eager))


def test_the_faults_tool_reads_both_planted_faults_over_a_limit_and_the_program_under(root, monkeypatch, capsys):
    """``benchmark/tools/faults_trinity.py``, the chip's check of the two faults ISSUE 35 names, rehearsed."""
    _fresh_programs(monkeypatch)
    tool = harness.load_module(os.path.join(root, "benchmark", "tools", "faults_trinity.py"))
    rc = tool.main(["--workload", CELL, "--seed", "77"], root=root, allow_cpu=True)
    _fresh_programs(monkeypatch)
    rows = [json.loads(line) for line in capsys.readouterr().out.strip().splitlines()]
    assert rc == 0 and [r["fault"] for r in rows] == ["none", "unwindowed", "full_rotary"]
    assert rows[0]["over"] == [] and all(r["over"] and r["as_expected"] for r in rows[1:])
    assert rows[0]["prompt_tokens"] == [4, 17, 118]   # under the window of 8, between 1 and 4, over 4
