"""The two cells' hot programs compile at real widths for a described v5e
chip (nothing runs; no chip time): the LoRA train step at 4 layers, the paged
decode step at B=64, C=8 and one prefill bucket at 8 layers. What the TPU's
compiler would refuse on the chip (memory, a kernel's tiling) it refuses here.

The topology is described inside a module fixture, never at import: only one
process may load the TPU's library, and every xdist worker imports this file.
"""

import os

import jax
import jax.numpy as jnp
import optax
import pytest
from jax.sharding import SingleDeviceSharding

import fixture_root

fixture_root.bench_imports()

import harness  # noqa: E402

HBM_LIMIT = 16.9e9  # bytes_limit memory_stats() reports on a v5e chip (PERF.md, PR 21)


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
def no_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache without one: keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


class _Ctx:
    def __init__(self, cell_name):
        cell = harness.Cell(fixture_root.REPO, cell_name)
        self.config, self.workload, self.traffic = cell.config, cell.workload, cell.traffic
        self.seed, self.out_dir = 1, "/tmp"


def _used_bytes(compiled):
    m = compiled.memory_analysis()
    return (m.temp_size_in_bytes + m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.generated_code_size_in_bytes)


def test_train_step_compiles_and_fits(topo, one_chip, no_cache, monkeypatch, tmp_path):
    import fedml_tpu.ops.flash_attention as fa
    from fedml_tpu.models.lora import lora_mask
    from fedml_tpu.parallel.fsdp import make_fsdp_train_step
    from fedml_tpu.parallel.ring_attention import active_mesh

    drv = harness.load_module(os.path.join(fixture_root.BENCH, "drivers", "llm_train.py"))
    ctx = _Ctx("mistral7b_lora_pack2k")
    ctx.out_dir = str(tmp_path)
    monkeypatch.setattr(fa, "_interpret", lambda: False)  # the Mosaic kernel, not its CPU emulation
    from fedml_tpu.train.llm import llm_trainer as lt

    real_init = lt.LLMTrainer.__init__
    monkeypatch.setattr(lt.LLMTrainer, "__init__",
                        lambda self, ma, da, ea, devices=None: real_init(self, ma, da, ea, devices=[topo.devices[0]]))
    trainer = drv.build_trainer(ctx)
    seq, batch = ctx.traffic["seq_len"], ctx.workload["program"]["batch_sequences"]
    dummy = jnp.zeros((1, seq), jnp.int32)
    shapes = jax.eval_shape(lambda k: trainer.model.init(k, dummy)["params"], jax.random.PRNGKey(0))
    params = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), shapes)
    labels = jax.tree.map(lambda m: "train" if m else "freeze", lora_mask(shapes))
    tx = optax.multi_transform({"train": trainer._full_tx, "freeze": optax.set_to_zero()}, labels)

    def apply_fn(p, tokens):
        with active_mesh(trainer.mesh):
            return trainer.model.apply({"params": p}, tokens)

    compile_step, _ = make_fsdp_train_step(apply_fn, tx, trainer.mesh, batch_axes=("dp", "fsdp"))
    opt = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
                       jax.eval_shape(tx.init, shapes))
    data = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=one_chip)
    mask = jax.ShapeDtypeStruct((batch, seq), jnp.float32, sharding=one_chip)
    compiled = compile_step(params, opt).lower(params, opt, data, mask).compile()
    assert "tpu_custom_call" in compiled.as_text()
    used = _used_bytes(compiled)
    print("train step bytes on the chip:", used, compiled.memory_analysis())
    assert 0.25 * 16e9 < used < HBM_LIMIT


def _serve_cfg():
    drv = harness.load_module(os.path.join(fixture_root.BENCH, "drivers", "llm_serve.py"))
    ctx = _Ctx("internlm2_7b_chat_open")
    cfg = drv.model_config(ctx)
    shapes = drv.param_shapes(cfg)
    return ctx, cfg, shapes


def _sds_tree(shapes, dtype, sharding):
    out = {}
    for path, shape in shapes.items():
        node = out
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    return out


def test_decode_step_compiles_and_fits(topo, one_chip, no_cache, monkeypatch):
    from fedml_tpu.serving import paged_kv
    from fedml_tpu.train.llm import generation

    ctx, cfg, shapes = _serve_cfg()
    p = ctx.workload["program"]
    B, C, ps = p["num_slots"], p["decode_chunk"], p["page_size"]
    n_pages = B * (cfg.max_seq_len // ps) + 1
    pcfg = paged_kv.paged_config(paged_kv.row_config(cfg), page_size=ps, num_pages=n_pages)
    params = _sds_tree(shapes, jnp.bfloat16, one_chip)
    monkeypatch.setattr(generation, "_COMPILED", {})
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # donate the pool, as on the chip
    step = paged_kv._paged_step_fn(pcfg, B, C)
    monkeypatch.undo()
    fn = getattr(step, "_fn", step)
    pool = {f"layer_{i}": {"attn": {
        "k": jax.ShapeDtypeStruct((n_pages, ps, cfg.n_kv_heads, cfg.head_dim), jnp.bfloat16, sharding=one_chip),
        "v": jax.ShapeDtypeStruct((n_pages, ps, cfg.n_kv_heads, cfg.head_dim), jnp.bfloat16, sharding=one_chip),
        "idx": jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)}} for i in range(cfg.n_layers)}

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    compiled = fn.lower(params, pool, s((B, cfg.max_seq_len // ps), jnp.int32), s((B,), jnp.int32),
                        s((B,), jnp.int32), s((B, 2), jnp.uint32), s((B,), jnp.float32),
                        s((B,), jnp.bool_)).compile()
    used = _used_bytes(compiled)
    print("decode step bytes on the chip:", used, compiled.memory_analysis())
    assert 0.25 * 16e9 < used < HBM_LIMIT


def test_prefill_bucket_compiles(topo, one_chip, no_cache, monkeypatch):
    from fedml_tpu.serving import paged_kv
    from fedml_tpu.train.llm import generation

    ctx, cfg, shapes = _serve_cfg()
    params = _sds_tree(shapes, jnp.bfloat16, one_chip)
    monkeypatch.setattr(generation, "_COMPILED", {})
    P_b = max(ctx.traffic["user_tokens"]["values"])
    fn = generation._prefill_fn(paged_kv.row_config(cfg), 1, P_b)
    compiled = fn.lower(params, jax.ShapeDtypeStruct((1, P_b), jnp.int32, sharding=one_chip),
                        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)).compile()
    used = _used_bytes(compiled)
    print("prefill bytes on the chip:", used)
    assert used < HBM_LIMIT
