"""``pangu_ultra_moe_chat_open``'s hot programs compile at the published widths
for a described v5e chip (nothing runs; no chip time): the paged decode step at
B=64, C=8 over the latent page pool with the routed layers' grouped matmuls,
the longest full prefill and the longest suffix pass, each with the Mosaic
kernels it should hold (``paged_latent_attention`` at 128 heads on a 576-wide
row in the step, ``grouped_matmul`` everywhere), and the memory each holds
beside 9.84 GB of weights. ``flops_pangu``'s counts are held to ISSUE 33's
table and to the program's own tree.

The topology is described inside a module fixture, never at import (see
``test_compile_real_widths.py``, whose pattern this follows for the new cell).
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import fixture_root

fixture_root.bench_imports()

import flops_pangu  # noqa: E402
import harness  # noqa: E402

HBM_LIMIT = 16.9e9
CELL = "pangu_ultra_moe_chat_open"


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def as_on_the_chip(monkeypatch):
    """Out of the persistent cache (a compile for a described chip cannot be
    read back), fresh program caches, and the code that asks for the backend
    told 'tpu': the kernels lower through Mosaic, the pool is donated."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    from fedml_tpu.models import mla, moe
    from fedml_tpu.train.llm import generation

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    monkeypatch.setattr(generation, "_COMPILED", {})
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mla._paged_latent_impl.cache_clear()
    moe._grouped_matmul_impl.cache_clear()
    yield
    monkeypatch.undo()
    mla._paged_latent_impl.cache_clear()
    moe._grouped_matmul_impl.cache_clear()
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


class _Ctx:
    def __init__(self):
        cell = harness.Cell(fixture_root.REPO, CELL)
        self.config, self.workload, self.traffic = cell.config, cell.workload, cell.traffic


def _cell():
    drv = harness.load_module(os.path.join(fixture_root.BENCH, "drivers", "llm_serve_pangu.py"))
    ctx = _Ctx()
    cfg = drv.model_config(ctx)
    return ctx, cfg, drv.param_shapes(cfg)


def _sds_tree(shapes, dtype, sharding):
    out = {}
    for path, shape in shapes.items():
        node = out
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    return out


def _used_bytes(compiled):
    m = compiled.memory_analysis()
    return (m.temp_size_in_bytes + m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.generated_code_size_in_bytes)


def _on(tree, sharding):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding), tree)


def _count(shapes, pick=lambda path: True):
    n = 0
    for path, shape in shapes.items():
        if pick(path):
            size = 1
            for s in shape:
                size *= s
            n += size
    return n


def test_config_is_the_published_one_and_the_counts_are_the_issues_table():
    ctx, cfg, shapes = _cell()
    c = ctx.config
    assert (cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.n_heads, cfg.vocab_size) == (5, 7680, 18432, 128, 19200)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim) == \
        (1536, 512, 128, 64, 128)
    assert cfg.layer_pattern == ("mla",) * 5 and [cfg.ffn_kind(i) for i in range(5)] == ["dense"] + ["routed"] * 4
    assert (cfg.moe_routed_experts, cfg.moe_held_experts, cfg.moe_rank, cfg.moe_top_k, cfg.moe_d_ff,
            cfg.moe_shared_experts, cfg.moe_routed_scaling, cfg.moe_norm_topk) == (256, 16, 0, 8, 2048, 1, 2.5, True)
    assert cfg.sandwich_norm and not cfg.tie_embeddings and cfg.norm_eps == 1e-5 and cfg.rope_theta == 25.6e6
    # ISSUE 33's table, to the parameter
    assert flops_pangu.mla_params(c) == 11796480 + 37748736 + 4423680 + 16777216 + 125829120 == 196575232  # 196.6 M
    assert flops_pangu.expert_params(c) == 3 * 7680 * 2048 == 47185920                                   # 47.19 M
    assert flops_pangu.router_params(c) == 7680 * 256 == 1966080
    assert flops_pangu.expert_layer_params(c) == 196575232 + 47185920 + 1966080 + 16 * 47185920 == 1000701952  # 1,000.7 M
    assert flops_pangu.dense_layer_params(c) == 196575232 + 3 * 7680 * 18432 == 621248512                 # 621.3 M
    assert flops_pangu.matmul_params(c) == 621248512 + 4 * 1000701952 + 2 * 19200 * 7680 == 4918968320  # 4,919 M
    # and the program's own tree: the matrices, and the norms beside them
    is_norm = lambda path: path.endswith("/scale")  # noqa: E731
    assert _count(shapes, lambda p: not is_norm(p)) == flops_pangu.matmul_params(c)
    assert _count(shapes, is_norm) == flops_pangu.norm_params(c) == 5 * (4 * 7680 + 1536 + 512) + 7680
    assert _count(shapes) == flops_pangu.total_params(c)
    assert abs(_count(shapes) / 4919e6 - 1.0) < 1e-3
    assert shapes["layer_1/moe/router"] == (7680, 256) and shapes["layer_1/moe/w_gate"] == (16, 7680, 2048)
    assert "layer_0/mlp/gate_proj/kernel" in shapes and "layer_0/moe/router" not in shapes
    # what a decode token-step must read: 3.50 GB whatever the routing, 94.4 MB a held expert hit, 5.76 KB a live token
    assert flops_pangu.non_expert_read_params(c) == 1751784960
    assert flops_pangu.decode_step_bytes(c, 0, 0) == 2 * 1751784960
    assert flops_pangu.decode_step_bytes(c, 1000, 40) == 2 * 1751784960 + 40 * 94371840 + 1000 * 5760
    assert flops_pangu.mla_decode_call_cost(c, 1000) == (2.0 * 128 * 1088 * 1000, 1000 * 1152.0)


def test_decode_step_compiles_over_the_latent_pool_and_fits(topo, one_chip, as_on_the_chip):
    from fedml_tpu.serving import paged_kv

    ctx, cfg, shapes = _cell()
    p = ctx.workload["program"]
    B, C, ps, n_pages = p["num_slots"], p["decode_chunk"], p["page_size"], p["num_pages"]
    pcfg = paged_kv.paged_config(paged_kv.row_config(cfg), page_size=ps, num_pages=n_pages)
    params = _sds_tree(shapes, jnp.bfloat16, one_chip)
    pool = _on(jax.eval_shape(lambda pr: paged_kv.paged_pool_init(pr, pcfg, B), _sds_tree(shapes, jnp.bfloat16, None)),
               one_chip)
    assert {k: v.shape for k, v in pool["layer_3"]["attn"].items()} == {"latent": (n_pages, ps, 640), "idx": ()}
    step = paged_kv._paged_step_fn(pcfg, B, C)
    fn = getattr(step, "_fn", step)

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    compiled = fn.lower(params, pool, s((B, cfg.max_seq_len // ps), jnp.int32), s((B,), jnp.int32),
                        s((B,), jnp.int32), s((B, 2), jnp.uint32), s((B,), jnp.float32),
                        s((B,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "paged_latent_attention" in text and "grouped_matmul" in text
    used = _used_bytes(compiled)
    print("pangu decode step bytes on the chip:", used, compiled.memory_analysis())
    # weights 9.84 GB + the latent pool 0.38 GB (held 640 lanes wide: 0.42) + temporaries
    assert 0.25 * 16e9 < used < HBM_LIMIT


@pytest.mark.parametrize("kind", ["full", "suffix"])
def test_longest_prefill_compiles_with_the_grouped_matmul(topo, one_chip, as_on_the_chip, kind):
    from fedml_tpu.serving import paged_kv
    from fedml_tpu.train.llm import generation

    ctx, cfg, shapes = _cell()
    p = ctx.workload["program"]
    params = _sds_tree(shapes, jnp.bfloat16, one_chip)
    turn = max(ctx.traffic["user_tokens"]["values"])
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    if kind == "full":
        T = turn + ctx.traffic["system_prompt_tokens"]
        fn = generation._prefill_fn(paged_kv.row_config(cfg), 1, T)
        compiled = fn.lower(params, jax.ShapeDtypeStruct((1, T), jnp.int32, sharding=one_chip), scalar).compile()
    else:
        pcfg = paged_kv.paged_config(paged_kv.row_config(cfg), page_size=p["page_size"], num_pages=p["num_pages"])
        row = {f"layer_{i}": {"attn": {"latent": jax.ShapeDtypeStruct((1, cfg.max_seq_len, 640), jnp.bfloat16),
                                       "idx": jax.ShapeDtypeStruct((), jnp.int32)}} for i in range(cfg.n_layers)}
        fn = paged_kv._suffix_prefill_fn(pcfg, turn)
        compiled = fn.lower(params, _on(row, one_chip), jax.ShapeDtypeStruct((1, turn), jnp.int32, sharding=one_chip),
                            scalar, scalar).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "grouped_matmul" in text
    used = _used_bytes(compiled)
    print(f"pangu {kind} prefill bytes on the chip:", used, compiled.memory_analysis())
    # the [128, T, S] float32 scores never exist: a key block's are [128, T, 256]
    assert used < HBM_LIMIT - 0.45e9  # beside the program's pool
