"""The page handoff (``jit_paged_admit``) compiles at each serving cell's own
shapes for a described v5e chip (nothing runs; no chip time): the page moves
are the Mosaic kernel ``page_handoff`` (one call a page group), every pool leaf
is aliased in to out, the program's temporaries are a few hundred KB, and no op
copies a leaf of a pool: what made the parent's whole-row scatter cost 14 ms an
admission at trinity's 4 kv heads were two whole-pool ``copy`` ops a leaf
(a relayout in, a relayout out).

The topology is described inside a module fixture, never at import (see
``test_compile_real_widths.py``, whose pattern this follows).
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

import fixture_root

fixture_root.bench_imports()

import harness  # noqa: E402

CELLS = ["internlm2_7b_chat_open", "jamba2_3b_chat_open", "pangu_ultra_moe_chat_open", "trinity_mini_longmix_over"]


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
    """Out of the persistent cache (a compile for a described chip cannot be read back), fresh program
    caches, and the code that asks for the backend told 'tpu': the kernel lowers through Mosaic, the pool is
    donated."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    from fedml_tpu.train.llm import generation

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    monkeypatch.setattr(generation, "_COMPILED", {})
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    yield
    monkeypatch.undo()
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


class _Ctx:
    def __init__(self, cell):
        self.config, self.workload, self.traffic = cell.config, cell.workload, cell.traffic


@pytest.mark.parametrize("name", CELLS)
def test_the_admit_program_moves_pages_in_place_at_the_cell_s_shapes(name, one_chip, as_on_the_chip):
    from fedml_tpu.models.transformer import TransformerLM
    from fedml_tpu.serving import paged_kv
    from fedml_tpu.train.llm.generation import _prefill_fn

    cell = harness.Cell(fixture_root.REPO, name)
    cfg = cell.driver().model_config(_Ctx(cell))
    p = cell.workload["program"]
    ps, B = p["page_size"], p["num_slots"]
    base = paged_kv.row_config(cfg)
    window = base.sliding_window if base.window_layers else 0
    n_blocks = base.max_seq_len // ps
    wpages = (B + 1) * paged_kv.window_bound(window, p["decode_chunk"], ps) + 1 if window else 0
    pcfg = paged_kv.paged_config(base, page_size=ps, num_pages=int(p.get("num_pages") or B * n_blocks + 1),
                                 window_pages=wpages)
    shapes = jax.eval_shape(lambda k: TransformerLM(base).init(k, jnp.zeros((1, 8), jnp.int32))["params"],
                            jax.random.PRNGKey(0))
    snap = np.int32(0) if base.has_recurrent_state else None
    row, first = jax.eval_shape(_prefill_fn(base, 1, 16), shapes, jnp.zeros((1, 16), jnp.int32), np.int32(16), snap)[:2]
    pool = jax.eval_shape(lambda: paged_kv.paged_pool_init(shapes, pcfg, B))
    on = lambda t: jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), t)  # noqa: E731
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    carry = (sds((B,), jnp.int32), sds((B,), jnp.int32), sds((B, 2), jnp.uint32))
    args = [on(pool), on(row), sds((n_blocks,), jnp.int32), sds((), jnp.int32), on(first), sds((), jnp.uint32),
            sds((), jnp.float32), carry, sds((), jnp.int32), sds((2 if window else 1, 2), jnp.int32)]
    if window:
        args.append(sds((n_blocks,), jnp.int32))
    compiled = paged_kv._paged_admit_fn(pcfg).lower(*args).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    groups = paged_kv._page_groups(pcfg, pool)
    assert len(groups) == (2 if window else 1) and all(groups)
    assert text.count('custom_call_target="tpu_custom_call"') == len(groups) and "page_handoff" in text
    pool_bytes = sum(int(np.prod(s.shape)) * s.dtype.itemsize for s in jax.tree.leaves(pool))
    assert pool_bytes <= mem.alias_size_in_bytes < pool_bytes + 4096      # every leaf aliased in to out
    assert mem.temp_size_in_bytes < 1 << 20                               # and no second copy of any
    leaf_shapes = {"[" + ",".join(str(d) for d in paged_kv._leaf_at(pool, path).shape) + "]"
                   for paths in groups for path in paths}
    copies = re.findall(r"= \w+(\[[\d,]*\])\S* copy\(", text)
    assert not leaf_shapes & set(copies), (leaf_shapes & set(copies))
    print(f"{name}: temp {mem.temp_size_in_bytes} B, alias {mem.alias_size_in_bytes} of {pool_bytes} B")
