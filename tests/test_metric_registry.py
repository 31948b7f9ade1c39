"""The exported-metric registry: every ``fedml_*`` series the tree emits,
by literal canonical name.

This file is one leg of the ``metric-registry`` fedlint rule's contract
(docs/static_analysis.md): a series is healthy only if it is emitted,
documented in docs/observability.md, AND asserted by at least one test.
Renaming a metric without touching this registry (and the doc) fails both
the rule and these tests — which is the point: dashboards and alerts key
on these exact strings.
"""

import os
import re

from fedml_tpu.core.telemetry import Telemetry
from fedml_tpu.core.telemetry import prom

# name -> Prometheus kind. Histograms are listed by base name (they render
# _bucket/_sum/_count); counters end in _total by construction.
EXPORTED = {
    # comm / resilience
    "fedml_comm_retry_total": "counter",
    "fedml_jax_compiles_total": "counter",
    "fedml_quorum_partial_total": "counter",
    "fedml_quorum_late_discarded_total": "counter",
    "fedml_quorum_surplus_total": "counter",
    "fedml_quorum_stale_accepted_total": "counter",
    "fedml_quorum_stale_rejected_total": "counter",
    "fedml_checkpoint_save_seconds": "histogram",
    "fedml_checkpoint_dropped_total": "counter",
    "fedml_client_health": "gauge",
    "fedml_client_straggler": "gauge",
    "fedml_straggler_total": "counter",
    # async / hierarchy aggregation
    "fedml_async_merges_total": "counter",
    "fedml_async_publishes_total": "counter",
    "fedml_async_staleness": "histogram",
    "fedml_async_buffer_depth": "gauge",
    "fedml_async_buffer_high_water": "gauge",
    "fedml_async_model_version": "gauge",
    "fedml_hierarchy_forwards": "gauge",
    "fedml_hierarchy_forwards_total": "counter",
    # per-link network telemetry (core/telemetry/netlink.py; all labeled
    # {src, dst, backend})
    "fedml_link_bandwidth_bytes_per_sec": "gauge",
    "fedml_link_rtt_seconds": "gauge",
    "fedml_link_loss_ratio": "gauge",
    "fedml_link_last_probe_age_seconds": "gauge",
    "fedml_link_bytes_sent": "gauge",
    "fedml_link_bytes_received": "gauge",
    "fedml_link_predicted_mib_seconds": "gauge",
    "fedml_link_confidence": "gauge",
    # SLO engine burn-rate alerts (core/telemetry/slo.py; gauges labeled
    # {slo} — burn_rate adds {window="fast"|"slow"})
    "fedml_alert_active": "gauge",
    "fedml_alert_transitions_total": "counter",
    "fedml_slo_burn_rate": "gauge",
    "fedml_slo_observed": "gauge",
    "fedml_slo_evaluations_total": "counter",
    # round engine / placement search
    "fedml_engine_rounds_total": "counter",
    "fedml_engine_round_seconds": "histogram",
    "fedml_placement_probes_total": "counter",
    "fedml_placement_search_seconds": "histogram",
    # pipelined round execution (core/pipeline/executor.py)
    "fedml_pipeline_items_total": "counter",
    "fedml_pipeline_stage_seconds": "histogram",
    "fedml_pipeline_stage_stall_seconds": "histogram",
    "fedml_pipeline_queue_depth": "histogram",
    "fedml_pipeline_overlap_frac": "histogram",
    # split learning front (fedml_tpu/split/api.py)
    "fedml_split_mb_loss": "histogram",
    "fedml_split_rounds_total": "counter",
    "fedml_split_partial_rounds_total": "counter",
    # server / mesh
    "fedml_server_aggregate_seconds": "histogram",
    "fedml_server_shard_bytes": "gauge",
    "fedml_device_hbm_peak_bytes": "gauge",
    # device-performance registry (core/telemetry/devperf.py; program gauges
    # labeled {program}, HBM gauges labeled {device})
    "fedml_device_mfu": "gauge",
    "fedml_device_flops_per_sec": "gauge",
    "fedml_device_hbm_bytes": "gauge",
    "fedml_device_hbm_high_water_bytes": "gauge",
    "fedml_program_flops_total": "counter",
    "fedml_program_steps_total": "counter",
    # training-dynamics observability (core/telemetry/modelwatch.py; client
    # gauges labeled {rank})
    "fedml_client_delta_norm": "gauge",
    "fedml_client_contribution": "gauge",
    "fedml_client_outlier_score": "gauge",
    "fedml_modelwatch_quarantined_total": "counter",
    "fedml_modelwatch_nan_rounds_total": "counter",
    # fleet-scale sketch telemetry (core/telemetry/sketches.py; quantile
    # gauges labeled {q}, offenders {rank} behind the cardinality budget,
    # series accounting labeled {family, state})
    "fedml_fleet_round_time_seconds": "gauge",
    "fedml_fleet_delta_norm": "gauge",
    "fedml_fleet_staleness": "gauge",
    "fedml_fleet_offender_round_seconds": "gauge",
    "fedml_fleet_clients_seen": "gauge",
    "fedml_fleet_straggler_ratio": "gauge",
    "fedml_fleet_outlier_rate": "gauge",
    "fedml_fleet_sketch_bytes": "gauge",
    "fedml_telemetry_series_live": "gauge",
    # privacy subsystem (core/privacy): windowed async SecAgg + accounted DP
    # (window gauges labeled {window, tier} when tier-scoped)
    "fedml_secagg_windows_total": "counter",
    "fedml_secagg_masked_merges_total": "counter",
    "fedml_secagg_dropouts_total": "counter",
    "fedml_secagg_recovered_total": "counter",
    "fedml_secagg_reveals_total": "counter",
    "fedml_secagg_windows_failed_total": "counter",
    "fedml_secagg_window_depth": "gauge",
    "fedml_secagg_windows": "gauge",
    "fedml_dp_noised_publishes_total": "counter",
    "fedml_dp_epsilon_spent": "gauge",
    "fedml_dp_budget_frac": "gauge",
    # training
    "fedml_llm_tokens_per_sec": "histogram",
    # serving
    "fedml_predictor_ready": "gauge",
    "fedml_serving_replicas": "gauge",
    "fedml_serving_request_seconds": "histogram",
    "fedml_serving_request_errors_total": "counter",
    "fedml_serving_cb_requests_total": "counter",
    "fedml_serving_cb_admissions_total": "counter",
    "fedml_serving_device_starved_ns_total": "counter",
    "fedml_serving_device_starvations_total": "counter",
    "fedml_serving_cb_tokens_generated_total": "counter",
    "fedml_serving_cb_ttft_seconds": "histogram",
    "fedml_serving_cb_tpot_seconds": "histogram",
    "fedml_serving_wasted_tokens_total": "counter",
    # paged KV cache + prefix sharing (serving/paged_kv.py + engine gauges)
    "fedml_serving_kv_pages": "gauge",               # {state=free|used|watermark}
    "fedml_serving_kv_prefix_nodes": "gauge",
    "fedml_serving_kv_prefix_hits_total": "counter",
    "fedml_serving_kv_prefix_misses_total": "counter",
    "fedml_serving_kv_prefix_evictions_total": "counter",
    "fedml_serving_kv_alloc_deferred_total": "counter",
    "fedml_serving_kv_admit_deferred_full_total": "counter",
    "fedml_serving_kv_admit_deferred_window_total": "counter",
    "fedml_serving_kv_window_pages_released_total": "counter",
    # recurrent-state snapshots of the prefix trie (models with Mamba layers)
    "fedml_serving_state_prefix_hits_total": "counter",
    "fedml_serving_state_prefix_misses_total": "counter",
    "fedml_serving_state_snapshots_total": "counter",
    "fedml_serving_state_snapshot_evictions_total": "counter",
    "fedml_serving_state_snapshot_bytes": "gauge",
    # latent pages and routed experts (models with MLA / RoutedMoE layers)
    "fedml_serving_kv_latent_bytes_live": "gauge",
    "fedml_serving_moe_tokens_routed_total": "counter",
    "fedml_serving_moe_local_picks_total": "counter",
    "fedml_serving_moe_experts_hit_total": "counter",
    "fedml_serving_moe_row_tiles_total": "counter",
    "fedml_serving_moe_load_imbalance": "gauge",
    # multi-tenant admission (serving/admission.py; {tenant}/{tenant,reason})
    "fedml_serving_admission_rejected_total": "counter",
    "fedml_serving_admission_deferrals_total": "counter",
    "fedml_serving_admission_burn_fraction": "gauge",
    "fedml_serving_tenant_usage_share": "gauge",
    "fedml_serving_tenant_budget_tokens": "gauge",
    "fedml_serving_tenant_ttft_p99_seconds": "gauge",
    # disaggregated prefill/decode pools (serving/replica_controller.py)
    "fedml_serving_pool_replicas": "gauge",          # {pool, state}
    "fedml_serving_pool_fallback_total": "counter",  # {pool}
    "fedml_serving_gateway_qps": "gauge",
    "fedml_serving_gateway_latency_ewma_seconds": "gauge",
    "fedml_serving_gateway_errors": "gauge",
    # telemetry internals
    "fedml_span_seconds_total": "counter",
    "fedml_span_count_total": "counter",
    "fedml_telemetry_dropped_total": "counter",
    "fedml_telemetry_trace_ctx_malformed_total": "counter",
}

_DOC = os.path.join(os.path.dirname(__file__), "..", "docs", "observability.md")


def test_names_are_canonical():
    for name, kind in EXPORTED.items():
        assert re.fullmatch(r"fedml_[a-z0-9_]+", name), name
        if kind == "counter":
            assert name.endswith("_total"), f"counter {name} must end _total"
        else:
            assert not name.endswith("_total"), name


def test_registry_matches_observability_doc():
    with open(_DOC, encoding="utf-8") as f:
        doc = f.read()
    missing = [n for n in EXPORTED if n not in doc]
    assert not missing, f"undocumented exported metrics: {missing}"


def test_prom_render_produces_registry_names():
    """Dotted telemetry names render to the registry's canonical prom
    families — the exact transform the whole registry relies on."""
    t = Telemetry(enabled=True)
    t.counter("quorum.partial").add(1)
    t.counter("serving.cb.requests").add(2)
    t.histogram("serving.cb.ttft_seconds").observe(0.01)
    t.histogram("llm.tokens_per_sec").observe(1234.0)
    text = prom.render(t, gauges=[("hierarchy_forwards", {"node": "leaf-0"}, 3.0)])
    assert "fedml_quorum_partial_total 1" in text
    assert "fedml_serving_cb_requests_total 2" in text
    assert "fedml_serving_cb_ttft_seconds_bucket" in text
    assert "fedml_serving_cb_ttft_seconds_count 1" in text
    assert "fedml_llm_tokens_per_sec_sum" in text
    assert 'fedml_hierarchy_forwards{node="leaf-0"} 3' in text


def test_registry_covers_live_exposition():
    """Every family a real render emits is registered (no unregistered
    series can sneak into /metrics via this path)."""
    t = Telemetry(enabled=True)
    t.counter("quorum.surplus").add(1)
    t.counter("checkpoint.dropped").add(1)
    t.histogram("server.aggregate_seconds").observe(0.2)
    text = prom.render(t)
    fams = set()
    for line in text.splitlines():
        if line.startswith("#") or not line:
            continue
        fam = line.split("{")[0].split(" ")[0]
        for suffix in ("_bucket", "_sum", "_count"):
            if fam.endswith(suffix) and fam[: -len(suffix)] in EXPORTED:
                fam = fam[: -len(suffix)]
        fams.add(fam)
    unregistered = {f for f in fams if f not in EXPORTED
                    and not f.startswith("fedml_span_")}
    assert not unregistered, f"unregistered families in exposition: {unregistered}"
