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
        out2 = flash_attention(q, k, v, causal=True)  # the blocks block_sizes() gives T=256
        np.testing.assert_allclose(np.asarray(out2), np.asarray(ref), atol=2e-5)

    def test_flash_untileable_shape_raises(self):
        """A caller that asked for the pallas kernel never silently gets
        einsum attention: blocks that do not tile the sequence raise."""
        from fedml_tpu.ops.flash_attention import flash_attention, tiles

        q, k, v = self._qkv(T=200)
        assert not tiles(200) and tiles(256) and tiles(100)
        with pytest.raises(ValueError, match="do not tile seq_len 200"):
            flash_attention(q, k, v, causal=True)

    def test_flash_blocks_must_divide_one_another(self):
        """The blocks the diagonal crosses are a static number only where one
        block size divides the other: explicit blocks that tile the sequence
        but not each other are refused like blocks that do not tile it."""
        from fedml_tpu.ops.flash_attention import flash_attention, tiles

        q, k, v = self._qkv(T=96)
        assert tiles(96, 48, 16) and tiles(96, 16, 48) and not tiles(96, 48, 32)
        with pytest.raises(ValueError, match="neither divides the other"):
            flash_attention(q, k, v, causal=True, block_q=48, block_k=32)
        with pytest.raises(ValueError, match="together, or neither"):
            flash_attention(q, k, v, causal=True, block_q=48)
        ref = xla_attention(q, k, v, causal=True)
        out = flash_attention(q, k, v, causal=True, block_q=48, block_k=16)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

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

    @pytest.mark.parametrize("causal", (True, False))
    @pytest.mark.parametrize("bq,bk", ((16, 16), (16, 32), (32, 16), (64, 16), (16, 64),
                                       (8, 8), (8, 32), (32, 8)))
    def test_flash_grads_match_xla(self, causal, bq, bk):
        # the Pallas backward kernels (dq + dkv) against einsum autodiff,
        # causal and dense, with uneven q/k block sizes: the diagonal then
        # crosses several blocks of the smaller side, and the unmasked /
        # masked split of each kernel's work is exercised on both sides; a
        # side of 8 makes 8 blocks of T = 64, past UNROLL_BLOCKS: the loop
        # with a traced block index instead of one program a block
        from fedml_tpu.ops.flash_attention import flash_attention

        q, k, v = self._qkv(T=64, D=16)
        g = jax.random.normal(jax.random.PRNGKey(7), q.shape, jnp.float32)

        def f_flash(q, k, v):
            return (flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk) * g).sum()

        def f_xla(q, k, v):
            return (xla_attention(q, k, v, causal=causal) * g).sum()

        got = jax.grad(f_flash, (0, 1, 2))(q, k, v)
        want = jax.grad(f_xla, (0, 1, 2))(q, k, v)
        for name, a, b in zip("dq dk dv".split(), got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5, err_msg=name)

    # (T, Hq, Hkv, D, dtype, causal): the DEFAULT block choice of every
    # kernel (``block_sizes``) against ``xla_attention``, forward and all
    # three gradients. The lengths walk the schedule's branches: the largest
    # rung with q-blocks that see zero, one and several wholly-unmasked
    # k-blocks before the diagonal (4096 looping; 2048, 1536, 1024 one
    # straight-line program a block), a length only the
    # smallest rung divides (384), one block below it (100, 8); MHA, G = 4,
    # and G = 8 on one kv head; f32 under "highest" and bf16; dense too.
    DEFAULT_BLOCK_CASES = (
        (4096, 2, 1, 16, "float32", True),   # 8 blocks a side: the looping path
        (2048, 2, 2, 16, "float32", True),
        (1536, 2, 1, 16, "float32", True),
        (1024, 4, 1, 16, "float32", True),
        (1024, 8, 1, 16, "bfloat16", True),
        (1024, 4, 1, 16, "float32", False),
        (384, 4, 1, 16, "float32", True),
        (384, 2, 2, 32, "bfloat16", True),
        (256, 8, 1, 64, "float32", False),
        (100, 8, 1, 16, "float32", True),
        (100, 4, 1, 16, "bfloat16", True),
        (8, 2, 2, 16, "float32", True),
    )

    @pytest.mark.parametrize("T,Hq,Hkv,D,dtype,causal", DEFAULT_BLOCK_CASES)
    def test_flash_default_blocks_match_xla(self, T, Hq, Hkv, D, dtype, causal):
        from fedml_tpu.models.transformer import repeat_kv
        from fedml_tpu.ops.flash_attention import flash_attention

        ks = jax.random.split(jax.random.PRNGKey(T + Hq), 4)
        q = jax.random.normal(ks[0], (1, T, Hq, D), jnp.float32).astype(dtype)
        k = jax.random.normal(ks[1], (1, T, Hkv, D), jnp.float32).astype(dtype)
        v = jax.random.normal(ks[2], (1, T, Hkv, D), jnp.float32).astype(dtype)
        g = jax.random.normal(ks[3], (1, T, Hq, D), jnp.float32)

        def f_flash(q, k, v):
            out = flash_attention(q, k, v, causal=causal)
            return (out.astype(jnp.float32) * g).sum(), out

        def f_xla(q, k, v):
            kr, vr = repeat_kv(k, v, Hq)
            out = xla_attention(q, kr, vr, causal=causal)
            return (out * g).sum(), out

        with jax.default_matmul_precision("highest"):
            (_, out), got = jax.value_and_grad(f_flash, (0, 1, 2), has_aux=True)(q, k, v)
            # the reference in f32 from the SAME (possibly bf16-rounded) inputs
            (_, ref), want = jax.value_and_grad(f_xla, (0, 1, 2), has_aux=True)(
                *(x.astype(jnp.float32) for x in (q, k, v)))
        # bf16: the kernel rounds p and ds to bf16 before the second matmul
        # (chip_smoke.py's tolerance and its reasoning)
        tol = 2e-2 if dtype == "bfloat16" else 5e-5
        for name, a, b in zip("o dq dk dv".split(), (out,) + got, (ref,) + want):
            a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
            assert np.isfinite(a).all(), name
            assert np.abs(a - b).max() / np.abs(b).max() < tol, name

    @pytest.mark.parametrize("T", (8, 64, 100, 128, 256, 384, 512, 1024, 1536, 2048, 4096, 16384))
    @pytest.mark.parametrize("kind", ("fwd", "dq", "dkv"))
    def test_block_sizes_divide_and_never_pass_the_sequence(self, T, kind):
        from fedml_tpu.ops.flash_attention import LADDER, block_sizes

        for D in (64, 128, 256):
            bq, bk = block_sizes(T, D, kind)
            assert T % bq == 0 and T % bk == 0 and bq <= T and bk <= T
            assert (bq, bk) == (T, T) if T < LADDER[-1] else (bq in LADDER and bk in LADDER)

    @pytest.mark.parametrize("kind", ("fwd", "dq", "dkv"))
    def test_block_sizes_monotone_in_the_sequence(self, kind):
        """A longer sequence of the same divisibility never gets a smaller
        block, and the choice reads the shape alone: no rung divides 200."""
        from fedml_tpu.ops.flash_attention import block_sizes

        chosen = [block_sizes(2 ** e, 128, kind) for e in range(3, 15)]
        for (bq0, bk0), (bq1, bk1) in zip(chosen, chosen[1:]):
            assert bq0 <= bq1 and bk0 <= bk1
        with pytest.raises(ValueError, match="tiles seq_len 200"):
            block_sizes(200, 128, kind)

    def test_block_sizes_shrink_when_the_tile_passes_its_budget(self):
        """head_dim enters through the tile's f32 temporaries: one so wide
        that a 512x512 tile's [block, D] slices pass the budget gets smaller
        blocks, which still divide T; the widths models have do not."""
        from fedml_tpu.ops.flash_attention import _TILE_BUDGET, _tile_bytes, block_sizes

        for kind in ("fwd", "dq", "dkv"):
            assert block_sizes(2048, 256, kind) == (512, 512)
            bq, bk = block_sizes(2048, 4096, kind)
            assert (bq, bk) != (512, 512) and 2048 % bq == 0 and 2048 % bk == 0
            assert _tile_bytes(kind, bq, bk, 4096) <= _TILE_BUDGET

    def test_block_sweep_tool_rehearses_on_the_cpu(self, tmp_path):
        """tools/flash_block_sweep.py (the table LADDER is chosen from) runs
        every kernel of this file and of a parent checkout's; without the chip
        it refuses, and its CPU rehearsal says what it is."""
        import json
        import os
        import sys

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        sys.path.insert(0, os.path.join(root, "tools"))
        try:
            import flash_block_sweep as sweep
        finally:
            sys.path.pop(0)
        out = tmp_path / "rows.jsonl"
        assert sweep.main(["--shapes", "cell", "--out", str(out)]) == 2  # a CPU time is no device time
        assert sweep.main(["--rehearse-cpu", "--shapes", "tiny", "--rungs", "128,256", "--reps", "1",
                           "--parent", root, "--out", str(out)]) == 0
        rows = [json.loads(line) for line in out.read_text().splitlines()]
        assert {(r["who"], r["kind"]) for r in rows} == {
            (w, k) for w in ("change", "parent") for k in ("fwd", "dq", "dkv")}
        assert all("error" not in r and r["err_vs_first"] < 5e-3 for r in rows)  # bf16: p is rounded after another max

    @pytest.mark.parametrize("T,ok", ((8, True), (100, True), (128, True), (256, True), (384, True),
                                      (1024, True), (1536, True), (2048, True), (16384, True),
                                      (200, False), (1000, False)))
    def test_tiles_accepts_what_the_smallest_rung_tiles(self, T, ok):
        from fedml_tpu.ops.flash_attention import block_sizes, tiles

        assert tiles(T) is ok
        if ok:  # whatever tiles() admits, every kernel has blocks for
            for kind in ("fwd", "dq", "dkv"):
                block_sizes(T, 128, kind)

    def test_flash_logs_its_blocks_once_a_shape(self, caplog):
        from fedml_tpu.ops import flash_attention as fa

        fa._chosen_blocks.cache_clear()
        q, k, v = self._qkv(T=24, D=8, H=2, B=1)
        with caplog.at_level("INFO", logger=fa.__name__):
            fa.flash_attention(q, k, v)
            fa.flash_attention(q, k, v)
            fa.flash_attention(q, k, v, block_q=8, block_k=8)  # explicit blocks: no choice made
        lines = [r.getMessage() for r in caplog.records if "flash_attention blocks" in r.getMessage()]
        assert len(lines) == 1
        assert "fwd=(24, 24)" in lines[0] and "T=24, D=8, dtype=float32, Hq=2, Hkv=2" in lines[0]

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
