"""``jamba2_3b_chat_open``'s decode step at B=64, C=8, compiled at the published
widths for a described v5e chip (nothing runs; no chip time): every Mamba
layer's one-token state update is the Mosaic call ``ssm_step`` over the live
slots, the pool's ``ssm`` leaves go into it in HBM and in place (no copy of a
``[64, 16, 5120]`` float32 state, no select over all 64 slots of one), and the
step still fits the chip.

The topology is described inside a module fixture, never at import, as
``test_compile_jamba_real_widths.py`` does; the step is built the way that file
builds it.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest

import fixture_root

fixture_root.bench_imports()

import harness  # noqa: E402

HBM_LIMIT = 16.9e9
CELL = "jamba2_3b_chat_open"


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def as_on_the_chip(monkeypatch):
    """Out of the persistent cache, fresh program caches, and the backend
    told 'tpu': the kernels lower through Mosaic, the pool is donated."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    from fedml_tpu.models import mamba, transformer

    def clear():
        for impl in (mamba._selective_scan_impl, mamba._ssm_step_impl, transformer._paged_attention_impl):
            impl.cache_clear()

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    clear()
    yield
    monkeypatch.undo()
    clear()
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


def _sds_tree(shapes, dtype, sharding):
    out = {}
    for path, shape in shapes.items():
        node = out
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    return out


def test_step_updates_the_live_slots_state_in_place(one_chip, as_on_the_chip):
    from fedml_tpu.models import mamba
    from fedml_tpu.serving import paged_kv

    cell = harness.Cell(fixture_root.REPO, CELL)
    drv = harness.load_module(os.path.join(fixture_root.BENCH, "drivers", "llm_serve_jamba.py"))
    cfg = drv.model_config(cell)
    shapes = drv.param_shapes(cfg)
    p = cell.workload["program"]
    B, C, ps = p["num_slots"], p["decode_chunk"], p["page_size"]
    pcfg = paged_kv.paged_config(paged_kv.row_config(cfg), page_size=ps, num_pages=p["num_pages"])
    assert mamba.step_takes_kernel(pcfg)
    pool = jax.eval_shape(lambda pr: paged_kv.paged_pool_init(pr, pcfg, B), _sds_tree(shapes, jnp.bfloat16, None))
    pool = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), pool)
    step = paged_kv._paged_step_fn(pcfg, B, C)

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    compiled = getattr(step, "_fn", step).lower(
        _sds_tree(shapes, jnp.bfloat16, one_chip), pool, s((B, cfg.max_seq_len // ps), jnp.int32),
        s((B,), jnp.int32), s((B,), jnp.int32), s((B, 2), jnp.uint32), s((B,), jnp.float32),
        s((B,), jnp.bool_)).compile()
    lines = compiled.as_text().splitlines()
    calls = [ln for ln in lines if re.match(r"\s*%ssm_step(\.\d+)? = .* custom-call\(", ln)]
    assert len(calls) == len(mamba.mamba_layers(cfg)) == 26
    # each returns the state in HBM (no memory space S(n) on its layout) aliased to its operand
    assert all("f32[64,16,5120]{2,1,0:T(8,128)})" in ln and "output_to_operand_aliasing={{1}:" in ln for ln in calls)
    # and nothing else computes, selects or copies a whole state: only the loop's plumbing holds one
    ops = {m.group(1) for ln in lines for m in [re.match(r"\s*(?:ROOT )?%\S+ = f32\[64,16,5120\]\S* ([a-z-]+)\(", ln)] if m}
    assert ops <= {"parameter", "get-tuple-element", "tuple", "bitcast"}, ops
    m = compiled.memory_analysis()
    used = (m.temp_size_in_bytes + m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.generated_code_size_in_bytes)
    assert 0.25 * 16e9 < used < HBM_LIMIT
