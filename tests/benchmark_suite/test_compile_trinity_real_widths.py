"""``trinity_mini_longmix_over``'s hot programs compile at the published widths
for a described v5e chip (nothing runs; no chip time): the paged decode step at
B=64, C=8 over the TWO page groups (the paged kernel with and without a start a
row) with the routed layers' grouped matmuls, the longest full prefill (16,640
tokens) and the longest suffix pass (16,384 behind a shared prefix), each with
the Mosaic kernels it should hold (``flash_attention_rows`` in the prefills,
``paged_attention`` in the step, ``grouped_matmul`` everywhere), and the memory
each holds beside 8.48 GB of weights.

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

import flops_trinity  # noqa: E402
import harness  # noqa: E402

HBM_LIMIT = 16.9e9
CELL = "trinity_mini_longmix_over"


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

    from fedml_tpu.models import moe, transformer
    from fedml_tpu.train.llm import generation

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    monkeypatch.setattr(generation, "_COMPILED", {})
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    caches = (moe._grouped_matmul_impl, transformer._row_attention_impl, transformer._paged_attention_impl)
    for c in caches:
        c.cache_clear()
    yield
    monkeypatch.undo()
    for c in caches:
        c.cache_clear()
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


class _Ctx:
    def __init__(self):
        cell = harness.Cell(fixture_root.REPO, CELL)
        self.config, self.workload, self.traffic = cell.config, cell.workload, cell.traffic


def _cell():
    drv = harness.load_module(os.path.join(fixture_root.BENCH, "drivers", "llm_serve_trinity.py"))
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
        leaf_dtype = jnp.float32 if parts[-1] == "router_bias" else dtype
        node[parts[-1]] = jax.ShapeDtypeStruct(shape, leaf_dtype, sharding=sharding)
    return out


def _used_bytes(compiled):
    m = compiled.memory_analysis()
    return (m.temp_size_in_bytes + m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.generated_code_size_in_bytes)


def _on(tree, sharding):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding), tree)


def _paged_cfg(ctx, cfg):
    from fedml_tpu.serving import paged_kv

    p = ctx.workload["program"]
    bound = paged_kv.window_bound(cfg.sliding_window, p["decode_chunk"], p["page_size"])
    return paged_kv.paged_config(paged_kv.row_config(cfg), page_size=p["page_size"], num_pages=p["num_pages"],
                                 window_pages=(p["num_slots"] + 1) * bound + 1), bound


def test_config_is_the_published_one_and_the_program_builds_it():
    ctx, cfg, shapes = _cell()
    c = ctx.config
    assert (cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.vocab_size) == \
        (5, 2048, 6144, 32, 4, 128, 200192)
    assert cfg.attn_kinds == ("window", "window", "full", "window", "window") and cfg.sliding_window == 2048
    assert cfg.window_layers == (0, 1, 3, 4) and not cfg.use_rope and cfg.qk_norm and cfg.attn_gate
    assert [cfg.ffn_kind(i) for i in range(5)] == ["dense"] + ["routed"] * 4
    assert (cfg.moe_routed_experts, cfg.moe_held_experts, cfg.moe_top_k, cfg.moe_d_ff, cfg.moe_shared_experts,
            cfg.moe_routed_scaling, cfg.moe_norm_topk, cfg.moe_select_bias) == (128, 128, 8, 1024, 1, 2.826, True, True)
    assert cfg.sandwich_norm and not cfg.tie_embeddings and cfg.norm_eps == 1e-5 and cfg.embed_scale == 2048 ** 0.5
    n = sum(int(jnp.prod(jnp.asarray(s))) for s in shapes.values())
    assert n == flops_trinity.total_params(c) and abs(n / 4.2415e9 - 1) < 1e-3     # 4.24 B: 8.48 GB in bfloat16
    assert shapes["layer_1/attn/q_proj/kernel"] == (2048, 4096) and shapes["layer_1/attn/k_proj/kernel"] == (2048, 512)
    assert shapes["layer_1/moe/w_gate"] == (128, 2048, 1024) and shapes["layer_1/moe/router"] == (2048, 128)


def test_decode_step_compiles_over_the_two_page_groups_and_fits(topo, one_chip, as_on_the_chip):
    from fedml_tpu.serving import paged_kv

    ctx, cfg, shapes = _cell()
    p = ctx.workload["program"]
    B, C, ps = p["num_slots"], p["decode_chunk"], p["page_size"]
    pcfg, bound = _paged_cfg(ctx, cfg)
    assert bound == 34 and pcfg.kv_window_pages == 65 * 34 + 1
    params = _sds_tree(shapes, jnp.bfloat16, one_chip)
    pool = _on(jax.eval_shape(lambda pr: paged_kv.paged_pool_init(pr, pcfg, B), _sds_tree(shapes, jnp.bfloat16, None)),
               one_chip)
    assert pool["layer_2"]["attn"]["k"].shape == (p["num_pages"], ps, 4, 128)            # the full group's pool
    assert pool["layer_3"]["attn"]["k"].shape == (pcfg.kv_window_pages, ps, 4, 128)      # the window group's
    step = paged_kv._paged_step_fn(pcfg, B, C)
    fn = getattr(step, "_fn", step)

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    table = s((B, cfg.max_seq_len // ps), jnp.int32)
    compiled = fn.lower(params, pool, table, s((B,), jnp.int32), s((B,), jnp.int32), s((B, 2), jnp.uint32),
                        s((B,), jnp.float32), s((B,), jnp.bool_), table).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "paged_attention" in text and "grouped_matmul" in text
    used = _used_bytes(compiled)
    print("trinity decode step bytes on the chip:", used, compiled.memory_analysis())
    # weights 8.48 GB + the full group 1.07 GB + the window group 1.16 GB + temporaries
    assert 0.25 * 16e9 < used < HBM_LIMIT - 2.5e9   # beside a prefill's temporaries


@pytest.mark.parametrize("kind", ["full", "suffix"])
def test_longest_prefill_compiles_with_the_rows_kernel(topo, one_chip, as_on_the_chip, kind):
    from fedml_tpu.serving import paged_kv
    from fedml_tpu.train.llm import generation

    ctx, cfg, shapes = _cell()
    params = _sds_tree(shapes, jnp.bfloat16, one_chip)
    turn = max(ctx.traffic["user_tokens"]["values"])
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    if kind == "full":
        T = turn + ctx.traffic["system_prompt_tokens"]
        fn = generation._prefill_fn(paged_kv.row_config(cfg), 1, T)
        compiled = fn.lower(params, jax.ShapeDtypeStruct((1, T), jnp.int32, sharding=one_chip), scalar).compile()
    else:
        pcfg, _ = _paged_cfg(ctx, cfg)
        kv = jax.ShapeDtypeStruct((1, cfg.max_seq_len, 4, 128), jnp.bfloat16)
        row = {f"layer_{i}": {"attn": {"k": kv, "v": kv, "idx": jax.ShapeDtypeStruct((), jnp.int32)}}
               for i in range(cfg.n_layers)}
        fn = paged_kv._suffix_prefill_fn(pcfg, turn)
        compiled = fn.lower(params, _on(row, one_chip), jax.ShapeDtypeStruct((1, turn), jnp.int32, sharding=one_chip),
                            scalar, scalar).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "flash_attention_rows" in text and "grouped_matmul" in text
    used = _used_bytes(compiled)
    print(f"trinity {kind} prefill bytes on the chip:", used, compiled.memory_analysis())
    # the [32, T, S] float32 scores never exist, nor the [T, vocab] logits: beside the two pools (2.3 GB)
    assert used < HBM_LIMIT - 2.3e9
