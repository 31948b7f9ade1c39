"""A temporary checkout for the tests: the real ``benchmark/`` directory and
``BENCHMARK.json`` copied, plus tiny cells that a CPU can hold. Files are only
ADDED to the copy, the way a later PR adds a cell."""

from __future__ import annotations

import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")

TINY_CONFIG = {
    "name": "tiny", "source": "test fixture", "hidden_size": 128, "intermediate_size": 256,
    "num_hidden_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "vocab_size": 512, "rms_norm_eps": 1e-05, "rope_theta": 10000.0,
}
TINY_TRAIN = {
    "driver": "llm_train",
    "program": {"lora_rank": 4, "lora_alpha": 16, "attention_impl": "pallas", "remat": True,
                "batch_sequences": 2, "learning_rate": 0.0002, "weight_decay": 0.0, "grad_clip": 1.0,
                "warmup_steps": 0, "max_steps": 100000},
    "min_steps": 2, "trace_steps": 2,
    "limits": {"loss_gap": 0.001, "grad_gap": 0.02, "grad_dir_gap": 0.00015, "delta_gap": 0.025,
               "frozen_moved": 0.0},
}
TINY_PACK = {"kind": "packed_documents", "seq_len": 256,
             "doc_len": {"median": 60, "sigma": 1.0, "min": 4, "max": 256}}
TINY_SERVE = {
    "driver": "llm_serve",
    "program": {"max_seq_len": 128, "num_slots": 4, "decode_chunk": 4, "page_size": 16,
                "client_threads": 8, "client_timeout_s": 60.0, "drain_s": 60.0},
    "check": {"sample_requests": 4, "pad_to": 128},
    "limits": {"widest_logit_gap": 0.02, "mean_logit_gap": 0.002},
}
TINY_CHAT = {"kind": "open_loop_chat", "rate_per_s": 4.0, "arrivals": "poisson",
             "system_prompt_tokens": 32, "system_prompt_share": 0.75,
             "user_tokens": {"values": [16, 32], "weights": [0.5, 0.5]},
             "max_new_tokens": {"values": [5, 9], "weights": [0.5, 0.5]}, "temperature": 0.0}


def add_cell(root, name, config, config_json, traffic, traffic_json, workload_json, e2e, why="test cell"):
    """Register a cell by adding files and entries only."""
    bpath = os.path.join(root, "BENCHMARK.json")
    with open(bpath) as f:
        b = json.load(f)
    bench = os.path.join(root, "benchmark")
    if config_json is not None:
        rel = f"benchmark/configs/{config}.json"
        with open(os.path.join(root, rel), "w") as f:
            json.dump(config_json, f)
        b["configs"].append({"name": config, "source": "test fixture", "file": rel, "reduced": [], "why": why})
    if traffic_json is not None:
        with open(os.path.join(bench, "traffic", traffic + ".json"), "w") as f:
            json.dump(traffic_json, f)
    with open(os.path.join(bench, "workloads", name + ".json"), "w") as f:
        json.dump(workload_json, f)
    b["workloads"].append({"name": name, "config": config, "traffic": traffic, "chips": 1, "why": why})
    for m in b["end_to_end"] + b["per_layer"]:
        if "workloads" in m and (m["name"] in e2e or m.get("moves") in e2e):
            m["workloads"].append(name)
    with open(bpath, "w") as f:
        json.dump(b, f)


def make_root(tmp) -> str:
    root = os.path.join(str(tmp), "checkout")
    os.makedirs(root)
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), os.path.join(root, "BENCHMARK.json"))
    add_cell(root, "tiny_lora", "tiny", TINY_CONFIG, "tiny_pack", TINY_PACK, TINY_TRAIN,
             {"train_tokens_per_s"})
    add_cell(root, "tiny_chat", "tiny", None, "tiny_chat", TINY_CHAT, TINY_SERVE,
             {"serve_latency_p95_ms", "serve_out_tokens_per_s"})
    return root


def bench_imports():
    """Put the benchmark's own modules (and the repo) on sys.path, as run.py does."""
    for p in (REPO, BENCH):
        if p not in sys.path:
            sys.path.insert(0, p)
