"""LLM path tests: transformer, LoRA plumbing, flash/ring attention parity,
FSDP train step on the virtual 8-device mesh, checkpoint round-trip."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fedml_tpu.models.transformer import TransformerConfig, TransformerLM, xla_attention
from fedml_tpu.models.lora import count_lora_params, lora_mask, merge_lora, split_lora

CFG = TransformerConfig(
    vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=128,
    max_seq_len=64, remat=False, lora_rank=4,
)


@pytest.fixture(scope="module")
def model_and_params():
    model = TransformerLM(CFG)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32))["params"]
    return model, params


class TestTransformer:
    def test_forward_shapes(self, model_and_params):
        model, params = model_and_params
        toks = jnp.ones((2, 16), jnp.int32)
        logits = model.apply({"params": params}, toks)
        assert logits.shape == (2, 16, 256)
        assert logits.dtype == jnp.float32

    def test_causality(self, model_and_params):
        """Changing a future token must not change past logits."""
        model, params = model_and_params
        t1 = jnp.zeros((1, 16), jnp.int32)
        t2 = t1.at[0, 10].set(7)
        l1 = model.apply({"params": params}, t1)
        l2 = model.apply({"params": params}, t2)
        np.testing.assert_allclose(np.asarray(l1[0, :10]), np.asarray(l2[0, :10]), atol=1e-4)
        assert not np.allclose(np.asarray(l1[0, 10:]), np.asarray(l2[0, 10:]), atol=1e-4)


class TestLoRA:
    def test_split_merge_roundtrip(self, model_and_params):
        _, params = model_and_params
        adapters, base = split_lora(params)
        n_lora, n_total = count_lora_params(params)
        assert n_lora > 0 and n_lora < 0.3 * n_total
        merged = merge_lora(base, adapters)
        flat_a = jax.tree_util.tree_leaves(merged)
        flat_b = jax.tree_util.tree_leaves(params)
        assert len(flat_a) == len(flat_b)
        for a, b in zip(flat_a, flat_b):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_mask_marks_only_adapters(self, model_and_params):
        _, params = model_and_params
        mask = lora_mask(params)
        flat = jax.tree_util.tree_flatten_with_path(mask)[0]
        marked = [p for p, v in flat if v]
        assert marked and all("lora" in "/".join(str(x) for x in p) for p, v in flat if v)


class TestAttentionImpls:
    def _qkv(self, T=32, D=16, H=4, B=2, seed=0):
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        shape = (B, T, H, D)
        return tuple(jax.random.normal(k, shape, jnp.float32) for k in ks)

    def test_flash_matches_xla(self):
        from fedml_tpu.ops.flash_attention import flash_attention

        q, k, v = self._qkv()
        ref = xla_attention(q, k, v, causal=True)
        out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def test_flash_gqa_matches_repeated_xla(self):
        # GQA-native kernel: 8 query heads over 2 kv heads, fwd + grads vs
        # the einsum path on repeat_kv'd tensors
        from fedml_tpu.models.transformer import repeat_kv
        from fedml_tpu.ops.flash_attention import flash_attention

        B, T, Hq, Hkv, D = 2, 64, 8, 2, 16
        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        q = jax.random.normal(ks[0], (B, T, Hq, D), jnp.float32)
        k = jax.random.normal(ks[1], (B, T, Hkv, D), jnp.float32)
        v = jax.random.normal(ks[2], (B, T, Hkv, D), jnp.float32)
        g = jax.random.normal(jax.random.PRNGKey(5), (B, T, Hq, D), jnp.float32)

        def f_flash(q, k, v):
            return (flash_attention(q, k, v, causal=True, block_q=16, block_k=16) * g).sum()

        def f_xla(q, k, v):
            kr, vr = repeat_kv(k, v, Hq)
            return (xla_attention(q, kr, vr, causal=True) * g).sum()

        out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
        kr, vr = repeat_kv(k, v, Hq)
        ref = xla_attention(q, kr, vr, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

        got = jax.grad(f_flash, (0, 1, 2))(q, k, v)
        want = jax.grad(f_xla, (0, 1, 2))(q, k, v)
        for name, a, b in zip("dq dk dv".split(), got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5, err_msg=name)

    def test_flash_nondefault_blocks_match_xla(self):
        """Explicit block sizes (the bench's attn_micro sweep passes them)
        stay numerically exact at a non-default, uneven config."""
        from fedml_tpu.models.transformer import repeat_kv
        from fedml_tpu.ops.flash_attention import flash_attention

        B, T, Hq, Hkv, D = 1, 256, 4, 2, 16
        ks = jax.random.split(jax.random.PRNGKey(21), 3)
        q = jax.random.normal(ks[0], (B, T, Hq, D), jnp.float32)
        k = jax.random.normal(ks[1], (B, T, Hkv, D), jnp.float32)
        v = jax.random.normal(ks[2], (B, T, Hkv, D), jnp.float32)
        kr, vr = repeat_kv(k, v, Hq)
        ref = xla_attention(q, kr, vr, causal=True)
        out = flash_attention(q, k, v, causal=True, block_q=64, block_k=256)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
        out2 = flash_attention(q, k, v, causal=True)  # the 128x128 constants
        np.testing.assert_allclose(np.asarray(out2), np.asarray(ref), atol=2e-5)

    def test_flash_untileable_shape_raises(self):
        """A caller that asked for the pallas kernel never silently gets
        einsum attention: blocks that do not tile the sequence raise."""
        from fedml_tpu.ops.flash_attention import flash_attention, tiles

        q, k, v = self._qkv(T=200)
        assert not tiles(200) and tiles(256) and tiles(100)
        with pytest.raises(ValueError, match="do not tile seq_len 200"):
            flash_attention(q, k, v, causal=True)

    def test_flash_bf16_under_ambient_highest_precision(self):
        """bf16 operands pin DEFAULT matmul precision: under an ambient
        ``highest`` context Mosaic rejects a bf16 contraction with fp32
        contract precision ("Bad lhs type" on v5e, PR 21) — the lowered
        kernel must not carry it."""
        from fedml_tpu.ops.flash_attention import flash_attention

        q, k, v = (x.astype(jnp.bfloat16) for x in self._qkv())

        def loss(q, k, v):
            return flash_attention(q, k, v, causal=True).astype(jnp.float32).sum()

        with jax.default_matmul_precision("highest"):
            jaxpr = str(jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(q, k, v))
        assert "pallas_call" in jaxpr
        assert "HIGHEST" not in jaxpr.split("pallas_call", 1)[1]

    def test_flash_grads_match_xla(self):
        # the Pallas backward kernels (dq + dkv) against einsum autodiff,
        # causal and dense, with uneven q/k block sizes to exercise the
        # causal block-skip logic on both sides of the diagonal
        from fedml_tpu.ops.flash_attention import flash_attention

        q, k, v = self._qkv(T=64, D=16)
        g = jax.random.normal(jax.random.PRNGKey(7), q.shape, jnp.float32)
        for causal in (True, False):
            for bq, bk in ((16, 16), (16, 32), (32, 16)):
                def f_flash(q, k, v, c=causal, bq=bq, bk=bk):
                    return (flash_attention(q, k, v, causal=c, block_q=bq, block_k=bk) * g).sum()

                def f_xla(q, k, v, c=causal):
                    return (xla_attention(q, k, v, causal=c) * g).sum()

                got = jax.grad(f_flash, (0, 1, 2))(q, k, v)
                want = jax.grad(f_xla, (0, 1, 2))(q, k, v)
                for name, a, b in zip("dq dk dv".split(), got, want):
                    np.testing.assert_allclose(
                        np.asarray(a), np.asarray(b), atol=5e-5,
                        err_msg=f"{name} causal={causal} bq={bq} bk={bk}",
                    )

    def test_remat_policies_agree(self):
        # remat is a memory/compute trade, never a numerics change: loss and
        # grads identical across none / full / dots policies
        from fedml_tpu.models.transformer import TransformerConfig, TransformerLM
        from fedml_tpu.parallel.fsdp import causal_lm_loss

        toks = jnp.asarray(np.random.default_rng(0).integers(0, 61, (2, 16)), jnp.int32)
        results = []
        for remat, policy in ((False, "full"), (True, "full"), (True, "dots")):
            cfg = TransformerConfig(
                vocab_size=61, d_model=32, n_layers=2, n_heads=4, n_kv_heads=4,
                d_ff=64, max_seq_len=16, dtype=jnp.float32, remat=remat,
                remat_policy=policy, lora_rank=0,
            )
            model = TransformerLM(cfg)
            params = model.init(jax.random.PRNGKey(0), toks)["params"]

            def loss(p, model=model):
                return causal_lm_loss(model.apply({"params": p}, toks), toks)

            l, g = jax.value_and_grad(loss)(params)
            results.append((float(l), g))
        for l, g in results[1:]:
            assert l == results[0][0]
            for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(results[0][1])):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_ring_matches_xla(self):
        # default layout (zigzag) and the classic contiguous layout are both
        # exact against the einsum reference
        from fedml_tpu.parallel.mesh import create_mesh
        from fedml_tpu.parallel.ring_attention import ring_attention

        q, k, v = self._qkv(T=32)
        mesh = create_mesh((4,), ("sp",))
        ref = xla_attention(q, k, v, causal=True)
        for layout in ("zigzag", "contiguous"):
            out = jax.jit(lambda q, k, v, l=layout: ring_attention(
                q, k, v, mesh, layout=l))(q, k, v)
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(ref), atol=2e-5, err_msg=layout)

    def test_ring_zigzag_grads_match_xla(self):
        from fedml_tpu.parallel.mesh import create_mesh
        from fedml_tpu.parallel.ring_attention import ring_attention

        q, k, v = self._qkv(T=32)
        mesh = create_mesh((4,), ("sp",))
        g = jax.random.normal(jax.random.PRNGKey(9), q.shape, jnp.float32)

        def loss_ring(q, k, v):
            return jnp.sum(ring_attention(q, k, v, mesh) * g)

        def loss_ref(q, k, v):
            return jnp.sum(xla_attention(q, k, v, causal=True) * g)

        gr = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
        gx = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gr, gx, ("dq", "dk", "dv")):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=5e-5, err_msg=name)

    def test_zigzag_reshard_roundtrip(self):
        # split then merge is the identity for any [B, Tl, ...] shard
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from fedml_tpu.parallel.mesh import create_mesh
        from fedml_tpu.parallel.ring_attention import _zigzag_merge, _zigzag_split

        mesh = create_mesh((4,), ("sp",))
        x = jax.random.normal(jax.random.PRNGKey(3), (2, 32, 4, 8), jnp.float32)

        def body(x):
            f, b = _zigzag_split(x, "sp", 4)
            return _zigzag_merge(f, b, "sp", 4)

        out = shard_map(body, mesh=mesh, in_specs=P(None, "sp"),
                        out_specs=P(None, "sp"))(x)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(x))

    def test_ring_odd_local_block_falls_back_contiguous(self):
        # Tl odd (T=28 over 4 devices -> Tl=7): zigzag needs an even local
        # block; the dispatcher must silently use the contiguous body and
        # stay exact
        from fedml_tpu.parallel.mesh import create_mesh
        from fedml_tpu.parallel.ring_attention import ring_attention

        q, k, v = self._qkv(T=28)
        mesh = create_mesh((4,), ("sp",))
        ref = xla_attention(q, k, v, causal=True)
        out = jax.jit(lambda q, k, v: ring_attention(q, k, v, mesh))(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


class TestFSDPTrainStep:
    @pytest.mark.slow
    def test_llm_trainer_loss_decreases_on_mesh(self, tmp_path):
        from fedml_tpu.train.llm.configurations import DatasetArguments, ExperimentArguments, ModelArguments
        from fedml_tpu.train.llm.llm_trainer import LLMTrainer

        ma = ModelArguments(
            vocab_size=128, d_model=32, n_layers=2, n_heads=4, n_kv_heads=4, d_ff=64,
            seq_len=32, lora_rank=0, remat=False,
        )
        ea = ExperimentArguments(
            max_steps=20, per_device_batch_size=2, learning_rate=5e-3, warmup_steps=2,
            dp=2, fsdp=2, tp=2, output_dir=str(tmp_path / "ckpt"),
        )
        tr = LLMTrainer(ma, DatasetArguments(), ea)
        metrics = tr.train()
        assert np.isfinite(metrics["final_loss"])
        assert metrics["steps"] == 20
        # checkpoint round-trip
        assert tr.ckpt.latest_step() == 20
        assert tr.restore() is True

    def test_lora_freezes_base(self, tmp_path):
        from fedml_tpu.train.llm.configurations import DatasetArguments, ExperimentArguments, ModelArguments
        from fedml_tpu.train.llm.llm_trainer import LLMTrainer
        from fedml_tpu.models.lora import split_lora

        ma = ModelArguments(
            vocab_size=128, d_model=32, n_layers=1, n_heads=4, n_kv_heads=4, d_ff=64,
            seq_len=16, lora_rank=4, remat=False,
        )
        ea = ExperimentArguments(
            max_steps=5, per_device_batch_size=2, dp=1, fsdp=1, tp=1, output_dir=str(tmp_path / "ckpt2")
        )
        tr = LLMTrainer(ma, DatasetArguments(), ea)
        tr._build(tr.init_params())
        _, base_before = split_lora(jax.device_get(tr.params))
        tr.train()
        adapters_after, base_after = split_lora(jax.device_get(tr.params))
        for a, b in zip(jax.tree.leaves(base_before), jax.tree.leaves(base_after)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert any(float(jnp.abs(l).sum()) > 0 for l in jax.tree.leaves(adapters_after))


class TestZigzagEdgeCases:
    def _qkv(self, T):
        # same construction as TestAttentionImpls._qkv, smaller defaults
        return TestAttentionImpls._qkv(self, T=T, B=1, H=2, D=8, seed=4)

    @pytest.mark.parametrize("n,T", [(1, 8), (2, 16), (8, 32)])
    def test_zigzag_exact_across_ring_widths(self, n, T):
        """n=1 (degenerate single-device ring: back chunk fully attends the
        front), n=2, and the full 8-wide virtual mesh all stay exact."""
        from fedml_tpu.parallel.mesh import create_mesh
        from fedml_tpu.parallel.ring_attention import ring_attention

        q, k, v = self._qkv(T=T)
        mesh = create_mesh((n,), ("sp",))
        ref = xla_attention(q, k, v, causal=True)
        out = jax.jit(lambda q, k, v: ring_attention(
            q, k, v, mesh, layout="zigzag"))(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, err_msg=f"n={n}")

    def test_unknown_layout_raises(self):
        from fedml_tpu.parallel.mesh import create_mesh
        from fedml_tpu.parallel.ring_attention import ring_attention

        q, k, v = self._qkv(T=16)
        mesh = create_mesh((2,), ("sp",))
        with pytest.raises(ValueError, match="unknown ring layout"):
            ring_attention(q, k, v, mesh, layout="zigzig")
