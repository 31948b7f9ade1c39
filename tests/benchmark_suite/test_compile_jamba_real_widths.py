"""``jamba2_3b_chat_open``'s hot programs compile at the published widths, all
28 layers, for a described v5e chip (nothing runs; no chip time): the paged
decode step at B=64, C=8 with the per-slot state beside the page pool, the
longest full prefill and the longest suffix pass, each with the Mosaic kernels
in it (the selective scan in the prefills, paged attention at 20 query heads on
1 kv head in the step), and the memory the step holds.

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

import harness  # noqa: E402

HBM_LIMIT = 16.9e9
CELL = "jamba2_3b_chat_open"


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

    from fedml_tpu.models import mamba, transformer
    from fedml_tpu.train.llm import generation

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    monkeypatch.setattr(generation, "_COMPILED", {})
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mamba._selective_scan_impl.cache_clear()
    transformer._paged_attention_impl.cache_clear()
    yield
    monkeypatch.undo()
    mamba._selective_scan_impl.cache_clear()
    transformer._paged_attention_impl.cache_clear()
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


class _Ctx:
    def __init__(self):
        cell = harness.Cell(fixture_root.REPO, CELL)
        self.config, self.workload, self.traffic = cell.config, cell.workload, cell.traffic


def _cell():
    drv = harness.load_module(os.path.join(fixture_root.BENCH, "drivers", "llm_serve_jamba.py"))
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


def _pool(cfg, pcfg, B, params_shapes, one_chip):
    """The cache pytree's shapes as the engine builds it (paged_pool_init)."""
    from fedml_tpu.serving import paged_kv

    params = _sds_tree(params_shapes, jnp.bfloat16, None)
    return _on(jax.eval_shape(lambda p: paged_kv.paged_pool_init(p, pcfg, B), params), one_chip)


def test_config_is_the_published_one():
    ctx, cfg, shapes = _cell()
    assert (cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.vocab_size) == \
        (28, 2560, 8192, 20, 1, 128, 65536)
    assert [i for i, k in enumerate(cfg.layer_pattern) if k == "attention"] == [7, 21]
    assert cfg.tie_embeddings and not cfg.use_rope and "lm_head/kernel" not in shapes
    n = sum(int(jnp.prod(jnp.asarray(s))) for s in shapes.values())
    import flops_jamba
    assert n == flops_jamba.total_params(ctx.config) == 3029337472  # 6.06 GB in bfloat16


def test_decode_step_compiles_with_both_kinds_of_state_and_fits(topo, one_chip, as_on_the_chip):
    from fedml_tpu.serving import paged_kv

    ctx, cfg, shapes = _cell()
    p = ctx.workload["program"]
    B, C, ps, n_pages = p["num_slots"], p["decode_chunk"], p["page_size"], p["num_pages"]
    pcfg = paged_kv.paged_config(paged_kv.row_config(cfg), page_size=ps, num_pages=n_pages)
    params = _sds_tree(shapes, jnp.bfloat16, one_chip)
    pool = _pool(cfg, pcfg, B, shapes, one_chip)
    state = pool["layer_0"]["mamba"]
    assert state["ssm"].shape == (B, 16, 5120) and state["ssm"].dtype == jnp.float32
    assert state["conv"].shape == (B, 3, 5120) and state["conv"].dtype == jnp.bfloat16
    assert pool["layer_7"]["attn"]["k"].shape == (n_pages, ps, 1, 128)
    step = paged_kv._paged_step_fn(pcfg, B, C)
    fn = getattr(step, "_fn", step)

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    compiled = fn.lower(params, pool, s((B, cfg.max_seq_len // ps), jnp.int32), s((B,), jnp.int32),
                        s((B,), jnp.int32), s((B, 2), jnp.uint32), s((B,), jnp.float32),
                        s((B,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "paged_attention" in text  # 20:1 heads tile: no gather + repeat_kv
    used = _used_bytes(compiled)
    print("jamba decode step bytes on the chip:", used, compiled.memory_analysis())
    # weights 6.06 GB + 64 slots' state 0.60 GB + pool 0.07 GB + temporaries: over the 25 % floor by the weights alone
    assert 0.25 * 16e9 < used < HBM_LIMIT


@pytest.mark.parametrize("kind", ["full", "suffix"])
def test_longest_prefill_compiles_with_the_scan_kernel(topo, one_chip, as_on_the_chip, kind):
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
        compiled = fn.lower(params, jax.ShapeDtypeStruct((1, T), jnp.int32, sharding=one_chip), scalar, scalar).compile()
    else:
        pcfg = paged_kv.paged_config(paged_kv.row_config(cfg), page_size=p["page_size"], num_pages=p["num_pages"])
        row = jax.eval_shape(lambda pr: generation._prefill_fn(paged_kv.row_config(cfg), 1, 64).__wrapped__(
            pr, jnp.zeros((1, 64), jnp.int32), jnp.int32(64))[0], _sds_tree(shapes, jnp.bfloat16, None))
        from fedml_tpu.models.mamba import PACKED

        # what _paged_gather_fn stages: the packed row without the snapshot a prefill leaves
        row = dict(row, **{PACKED: {k: v for k, v in row[PACKED].items() if not k.startswith("snap_")}})
        fn = paged_kv._suffix_prefill_fn(pcfg, turn)
        compiled = fn.lower(params, _on(row, one_chip), jax.ShapeDtypeStruct((1, turn), jnp.int32, sharding=one_chip),
                            scalar, scalar, scalar).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "selective_scan" in text
    used = _used_bytes(compiled)
    print(f"jamba {kind} prefill bytes on the chip:", used)
    assert used < HBM_LIMIT
