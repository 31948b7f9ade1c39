"""ops/paged_attention.py: the kernel (interpreted here) against a plain f32
reference written row by row; garbage outside a row's live positions changes
nothing; the paged decode step equals the gather + repeat + mask formulation
token for token and holds no context-sized copy; the Mosaic form compiles for
a described v5e chip at the chat cell's shapes."""

import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.models import transformer
from fedml_tpu.models.transformer import TransformerConfig, TransformerLM
from fedml_tpu.ops import paged_attention as pa
from fedml_tpu.serving import paged_kv
from fedml_tpu.train.llm import generation

PS, N_BLOCKS, N_PAGES, D, N_KV = 8, 6, 40, 32, 2
FULL = PS * N_BLOCKS
HUGE = 1e30  # finite in f32 and in bf16

# row -> (length, what it pins); rows 5 and 6 map the same two prefix pages
ROWS = {
    "inactive": 0, "one": 1, "page": PS, "page_plus_1": PS + 1, "full": FULL,
    "shared_a": 2 * PS + 3, "shared_b": 3 * PS + 5,
}


def _tables():
    """Every row its own pages, unowned entries the trash page 0, except that
    the two ``shared`` rows map one pair of prefix pages."""
    bt = np.zeros((len(ROWS), N_BLOCKS), np.int32)
    nxt = 1
    for b, length in enumerate(ROWS.values()):
        for j in range(-(-length // PS)):
            bt[b, j] = nxt
            nxt += 1
    a, b = list(ROWS).index("shared_a"), list(ROWS).index("shared_b")
    bt[b, :2] = bt[a, :2]
    assert nxt <= N_PAGES - 1  # the last page stays unowned: garbage goes there
    return bt


def _inputs(G, dtype):
    ks = jax.random.split(jax.random.PRNGKey(G), 3)
    q = jax.random.normal(ks[0], (len(ROWS), N_KV * G, D), dtype)
    k = jax.random.normal(ks[1], (N_PAGES, PS, N_KV, D), dtype)
    v = jax.random.normal(ks[2], (N_PAGES, PS, N_KV, D), dtype)
    return q, k, v, _tables(), np.asarray(list(ROWS.values()), np.int32)


def _plain(q, k_pool, v_pool, bt, lens):
    """Row by row, head by head, in f32 numpy: softmax(q k^T / sqrt(D)) v over
    the row's own first ``len`` positions."""
    q, k_pool, v_pool = (np.asarray(x, np.float32) for x in (q, k_pool, v_pool))
    B, H, _ = q.shape
    G = H // k_pool.shape[2]
    out = np.zeros_like(q)
    for b in range(B):
        n = int(lens[b])
        if n == 0:
            continue
        k = k_pool[bt[b]].reshape(-1, *k_pool.shape[2:])[:n]
        v = v_pool[bt[b]].reshape(-1, *v_pool.shape[2:])[:n]
        for h in range(H):
            s = k[:, h // G] @ q[b, h] / math.sqrt(D)
            p = np.exp(s - s.max())
            out[b, h] = (p / p.sum()) @ v[:, h // G]
    return out


_kernel = jax.jit(pa.paged_attention)


def _planted(k, v, bt, lens):
    """Huge finite values everywhere a row must not look: the trash page, the
    unowned page, the tail of every row's last page. The full row's table has
    no dead entry; every other row's dead entries point at the trash page."""
    k, v = np.array(k, np.float32), np.array(v, np.float32)
    for pool in (k, v):
        pool[0] = HUGE
        pool[N_PAGES - 1] = -HUGE
        for b, n in enumerate(lens):
            if n % PS:
                pool[bt[b, n // PS], n % PS:] = HUGE
    return k, v


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("G", [1, 4])
@pytest.mark.parametrize("row", list(ROWS))
def test_kernel_matches_plain_reference(row, G, dtype):
    q, k, v, bt, lens = _inputs(G, dtype)
    b = list(ROWS).index(row)
    out = np.asarray(_kernel(q, k, v, bt, lens), np.float32)
    want = _plain(q, k, v, bt, lens)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2  # bf16: probabilities and the result round to 8 bits
    np.testing.assert_allclose(out[b], want[b], atol=tol, rtol=tol)
    # garbage outside the row's live positions: not one bit of the output moves
    kg, vg = _planted(k, v, bt, lens)
    # a dead table entry may also name a page full of garbage
    btg = np.where(np.arange(N_BLOCKS)[None] >= -(-lens[:, None] // PS), N_PAGES - 1, bt)
    got = np.asarray(_kernel(q, jnp.asarray(kg, dtype), jnp.asarray(vg, dtype), btg, lens), np.float32)
    np.testing.assert_array_equal(got[b], out[b])
    if lens[b] == 0:
        # an inactive row reads no page: a pool of NaN leaves it finite
        nan = jnp.full_like(k, jnp.nan)
        assert np.isfinite(np.asarray(_kernel(q, nan, nan, bt, lens), np.float32)[b]).all()


@pytest.mark.parametrize("G", [1, 4])
def test_reference_formulation_matches_plain_reference(G):
    q, k, v, bt, lens = _inputs(G, jnp.float32)
    out = np.asarray(pa.paged_attention_reference(q, k, v, bt, lens))
    act = lens > 0
    np.testing.assert_allclose(out[act], _plain(q, k, v, bt, lens)[act], atol=2e-6, rtol=2e-6)


def test_tiles_rule():
    assert pa.tiles(128, 16, 32, 8, jnp.bfloat16)
    assert pa.tiles(128, 16, 32, 32, jnp.bfloat16)     # G = 1
    assert pa.tiles(256, 8, 8, 2, jnp.float32)
    assert not pa.tiles(64, 16, 32, 8, jnp.bfloat16)   # head_dim under a vreg's lanes
    assert not pa.tiles(128, 8, 32, 8, jnp.bfloat16)   # half a bf16 sublane tile
    assert not pa.tiles(128, 16, 12, 8, jnp.bfloat16)  # heads do not group
    assert pa.pages_per_block(16, 8, 128) == 8
    assert pa.pages_per_block(16, 32, 128) == 2
    assert pa.pages_per_block(16, 128, 128) == 1
    assert pa.pages_per_block(4, 2, 6) == 6            # never past the table


def test_impl_is_decided_from_platform_and_shape(caplog):
    transformer._paged_attention_impl.cache_clear()
    with caplog.at_level("INFO", logger=transformer.log.name):
        assert transformer._paged_attention_impl("cpu", 16, 4, 4, 2, "float32") is pa.paged_attention
        assert transformer._paged_attention_impl("tpu", 128, 16, 32, 8, "bfloat16") is pa.paged_attention
        assert transformer._paged_attention_impl("tpu", 64, 16, 32, 8, "bfloat16") is pa.paged_attention_reference
        transformer._paged_attention_impl("tpu", 64, 16, 32, 8, "bfloat16")  # logged once a case
    msgs = [r.getMessage() for r in caplog.records]
    assert sum("reference formulation" in m and "head_dim=64" in m for m in msgs) == 1
    assert sum("pallas kernel" in m for m in msgs) == 2
    transformer._paged_attention_impl.cache_clear()


# --- the decode step ---------------------------------------------------------

CFG = TransformerConfig(
    vocab_size=97, d_model=96, n_layers=2, n_heads=6, n_kv_heads=2, d_ff=128,
    max_seq_len=40, dtype=jnp.bfloat16, remat=False, lora_rank=0,
)
B, C = 3, 4
PCFG = paged_kv.paged_config(CFG, page_size=4, num_pages=23)


@pytest.fixture(scope="module")
def params():
    p = TransformerLM(CFG).init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"]
    return jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16), p)


def _step_args(params):
    tables = np.zeros((B, PCFG.max_seq_len // PCFG.kv_page_size), np.int32)
    tables[0, :10] = np.arange(1, 11)
    tables[1, :10] = np.arange(11, 21)  # row 2 is a free slot: all trash
    return (params, paged_kv.paged_pool_init(params, PCFG, B), jnp.asarray(tables),
            jnp.asarray([5, 7, 0], jnp.int32), jnp.asarray([0, 0, 9], jnp.int32),  # stale length on the free slot
            jnp.asarray(np.arange(2 * B).reshape(B, 2), jnp.uint32), jnp.zeros((B,), jnp.float32),
            jnp.asarray([True, True, False]))


def _fresh_step(monkeypatch, impl=None):
    """``_paged_step_fn`` traced anew, with the named formulation in place of
    what the platform would choose."""
    monkeypatch.setattr(generation, "_COMPILED", {})
    if impl is not None:
        monkeypatch.setattr(transformer, "_paged_attention_impl", lambda *a: impl)
    step = paged_kv._paged_step_fn(PCFG, B, C)
    return getattr(step, "_fn", step)


def test_paged_step_equals_reference_formulation(params, monkeypatch):
    """Greedy, three chunks from empty rows: the same tokens from both
    formulations; the logits of a step over the filled pool within bf16
    rounding."""
    runs = {}
    for name, impl in (("kernel", pa.paged_attention), ("reference", pa.paged_attention_reference)):
        step = _fresh_step(monkeypatch, impl)
        _, pool, tables, tok, lengths, keys, temps, active = _step_args(params)
        toks = []
        for _ in range(3):
            pool, tok, lengths, keys, out = step(params, pool, tables, tok, lengths, keys, temps, active)
            toks.append(np.asarray(out))
        logits = jax.jit(lambda pool, tok, lengths: generation.decode_model(PCFG).apply(
            {"params": params, "cache": pool}, tok[:, None], positions=lengths[:, None],
            cache_idx=jnp.where(active, lengths, -1), block_tables=tables, mutable=["cache"])[0])(pool, tok, lengths)
        runs[name] = (np.concatenate(toks, 1), np.asarray(lengths), np.asarray(logits[:, 0]))
    (tk, lk, gk), (tr, lr, gr) = runs["kernel"], runs["reference"]
    np.testing.assert_array_equal(lk, [3 * C, 3 * C, 9])  # the free slot's stale length stays
    np.testing.assert_array_equal(tk[:2], tr[:2])
    assert (tk[2] == 0).all()
    assert np.abs(gk[:2] - gr[:2]).max() <= 2 ** -6 * np.abs(gr[:2]).max()


def _tensor_sizes(text):
    return {math.prod(int(d) for d in m.group(1).split("x"))
            for m in re.finditer(r"tensor<((?:\d+x)*\d+)x[a-z]", text)}


def test_paged_step_holds_no_context_sized_copy(params, monkeypatch):
    """The gain rests on an absence: no array of B x S x n_heads x head_dim
    (the repeated copy) and none of B x S x n_kv_heads x head_dim (the gathered
    one) in the lowered step on the kernel path. The reference formulation,
    lowered the same way, shows both: the scan would see them."""
    S = PCFG.max_seq_len
    repeated = B * S * CFG.n_heads * CFG.head_dim
    gathered = B * S * CFG.n_kv_heads * CFG.head_dim
    args = _step_args(params)
    kernel = _tensor_sizes(_fresh_step(monkeypatch).lower(*args).as_text())
    assert repeated not in kernel and gathered not in kernel
    plain = _tensor_sizes(_fresh_step(monkeypatch, pa.paged_attention_reference).lower(*args).as_text())
    assert repeated in plain and gathered in plain


def test_chunk_span_counts_pages_read(params):
    from fedml_tpu.core import telemetry as tel
    from fedml_tpu.serving.continuous_batching import PagedContinuousBatchingEngine

    registry = tel.get_telemetry()
    was = registry.enabled
    registry.set_enabled(True)  # whatever an earlier file of this worker left it at
    last = registry.snapshot()["spans"][-1:]
    seq0 = last[0]["seq"] if last else 0
    eng = PagedContinuousBatchingEngine(params, CFG, num_slots=2, chunk=2, page_size=4)
    try:
        eng.submit(list(range(1, 8)), 5).result(timeout=120)  # 7 prompt tokens, 5 new
    finally:
        eng.shutdown()
        registry.set_enabled(was)
    chunks = [r for r in tel.snapshot()["spans"]
              if r["seq"] > seq0 and r["name"] == "serving.cb.chunk"]
    # one live row; the first step of a chunk at length L reads ceil((L + 1) / 4) pages
    assert [c["attrs"]["slots"] for c in chunks] == [1, 1]
    assert [c["attrs"]["pages"] for c in chunks] == [2, 3]  # L = 7, then 9


# --- the Mosaic form, compiled for a described chip ---------------------------


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
def no_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache without one: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


@pytest.mark.parametrize("n_kv", [8, 32], ids=["gqa_32_8", "mha_32_32"])
def test_mosaic_kernel_compiles_at_the_chat_cells_shapes(one_chip, no_cache, monkeypatch, n_kv):
    """B 64, 32 query heads of 128, pages of 16, 128 blocks a row, bf16: what
    the chip's compiler would refuse (tiling, VMEM, the DMAs) it refuses
    here. The benchmark's own decode compile test lowers with the CPU's
    ``default_backend()`` and sees the interpreted form only."""
    monkeypatch.setattr(pa, "_interpret", lambda: False)

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    pool = s((4097, 16, n_kv, 128), jnp.bfloat16)
    compiled = jax.jit(pa.paged_attention).lower(
        s((64, 32, 128), jnp.bfloat16), pool, pool, s((64, 128), jnp.int32), s((64,), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    # the pool reaches the kernel as it lies in HBM: its reshape is a bitcast
    assert not re.search(r"= bf16\[4097,[\d,]+\]\S* (copy|fusion|transpose)\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
