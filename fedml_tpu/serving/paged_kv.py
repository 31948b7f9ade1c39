"""Paged KV cache: a block-pool allocator + the paged decode executables,
and beside the pages the per-slot state of recurrent layers.

A pool of one full [max_seq_len] KV row per slot sizes HBM for the worst-case
sequence times ``num_slots`` and stores a common system prompt once PER
REQUEST. The engine (serving/continuous_batching.py) keeps K/V in a
vLLM-style page pool instead:

- the physical cache is [kv_num_pages, kv_page_size, kv, hd] per layer —
  ONE pool shared by every in-flight request; page 0 is a reserved trash
  page (never allocated) so unowned block-table entries have a harmless
  scatter/gather target;
- each request owns a BLOCK TABLE (host list of page ids, padded with 0)
  mapping logical position ``l`` to page ``table[l // page_size]``; the
  tables ride the decode step as RUNTIME data (``_paged_step_fn``), so one
  executable per (cfg, B, C) serves every admission mix — zero retrace,
  pinned by ``track_compiles("paged_step")``;
- pages are REFCOUNTED and prompt prefixes are hash-consed on token-chunk
  (page) boundaries: requests sharing a system prompt map the same
  physical pages. Shared pages are mapped copy-on-write in the degenerate
  sense that a copy is never needed — only FULL prompt chunks are
  registered, so the first writable position (the prompt tail / decode
  stream) always lands in a page with refcount 1;
- a free-list allocator with an admission watermark: when free pages run
  low, LRU prefix retentions are evicted first, and admission defers (the
  request stays queued) rather than corrupt in-flight decode. Occupancy,
  watermark, and hit/eviction counts are exported to telemetry.

TWO KINDS OF STATE live in the one cache pytree the decode step carries
(``TransformerConfig.layer_pattern``). An attention layer's leaves are the
page pool above. A recurrent (Mamba) layer's leaves, named in
``models/mamba.STATE_LEAVES``, are ``[num_slots, ...]`` arrays indexed by the
request's SLOT: never paged, written whole at admission, carried and donated
with the pool by ``_paged_step_fn``, left behind at release (the next
admission overwrites the slot). Prefix sharing needs more than pages for
them: the shared tokens can be skipped only from the exact state at the
shared boundary, so a trie node may hold a SNAPSHOT of that state
(``PagedKVAllocator.match`` / ``attach_state``), counted in bytes against a
budget and evicted LRU; a match is usable as deep as its deepest node that
holds one. Snapshots arise without priming: a prefill that diverges from the
trie at a node without one emits the state at that boundary
(``snap_lens``), and the node keeps it.

TWO PAGE GROUPS where the model has window layers (``TransformerConfig.
attn_kinds``). A full layer needs every token of a request, a window layer only
the ``sliding_window`` newest, so their K/V live in pools of their own page
counts (``kv_num_pages`` / ``kv_window_pages``), with block tables, free lists
and refcounts of their own. Both tables index a request's LOGICAL blocks (a
sliding table: position ``l`` is at ``table[l // page_size]`` in either), but
the window group's entries behind the request's horizon point at the trash
page again: the engine hands those pages back while the request decodes
(``PagedContinuousBatchingEngine._slide_windows``), so a live request holds at
most ``window_bound`` window pages whatever its length. The admit program
moves one runtime range of a finished prefill row's blocks a group: the
prompt's blocks behind the shared prefix into the full group, only the blocks
of its last ``sliding_window`` tokens into the window group. The prefix trie's
nodes hold a page of each group (the window page where some request's window
reached over the chunk when it registered it): a hit needs the window pages of
the blocks its suffix pass can still see and takes a reference on those alone;
a shared window page that a request's horizon passes loses that request's
reference, not the trie's. A model with no window layers has one group and
the tables it always had.

THE PAGE HANDOFF. Prefill reuses the contiguous executables
(`generation._prefill_fn`) at B=1, and a finished row goes into pages through
``_paged_admit_fn``: of each page group it moves the blocks the request OWNS
(the ranges above, operands of the one executable), a DMA a page from the row
to its page id in every leaf of the group, the pools updated in place
(``ops/page_handoff.rows_to_pages``). The row is ``max_seq_len`` long whatever
the prompt; the handoff's time follows the prompt, because a block outside the
range is neither read nor written (a scatter of all ``max_seq_len /
page_size`` blocks, the unowned ones onto the trash page, made XLA lay both
pools out anew around it at 4 kv heads: 14.4 ms an admission at a 16,896-token
row, all but 0.8 of them whole-pool copies). A prefix HIT skips recomputing
the shared prompt: gather the request's pages back into a contiguous row
(``_paged_gather_fn``: the whole table, at what the row's bytes cost), rewind
the write index to the shared length, and run one multi-token decode-mode pass
over just the suffix (``_suffix_prefill_fn``): the transformer's scalar-index
branch already supports a runtime start position, so suffix lengths share
16-token-bucketed executables exactly like fresh prefills.
"""

from __future__ import annotations

import dataclasses
import heapq
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..core import telemetry as tel
from ..core.telemetry import devperf, track_compiles, tsdb
from ..models.mamba import PACKED, SNAPSHOT_LEAVES, STATE_LEAVES, mamba_layers, pack_state, unpack_state
from ..models.transformer import TransformerConfig
from ..ops.page_handoff import rows_to_pages
from ..train.llm.generation import (
    _leaf_at,
    _leaf_name,
    _lru_get,
    _mutable,
    _rewind_cache,
    _routing,
    _sample,
    decode_model,
)

#: reserved trash page: scatter target for every unowned block-table entry
TRASH_PAGE = 0


def paged_config(cfg: TransformerConfig, *, page_size: int,
                 num_pages: int, window_pages: int = 0) -> TransformerConfig:
    """The paged-decode twin of a config (same params). ``window_pages``: the
    window group's page count, for a model with window layers."""
    if page_size < 1:
        raise ValueError(f"page_size must be >= 1, got {page_size}")
    if cfg.max_seq_len % page_size != 0:
        raise ValueError(
            f"max_seq_len {cfg.max_seq_len} must be a multiple of "
            f"page_size {page_size} (block tables cover whole pages)")
    if num_pages < 2:
        raise ValueError(
            f"num_pages must be >= 2 (page {TRASH_PAGE} is reserved trash), "
            f"got {num_pages}")
    if bool(cfg.window_layers) != (window_pages > 0) or window_pages == 1:
        raise ValueError(
            f"window_pages must be >= 2 for a model with window layers and 0 "
            f"for one without, got {window_pages} beside {len(cfg.window_layers)} window layers")
    return dataclasses.replace(
        cfg, kv_page_size=int(page_size), kv_num_pages=int(num_pages),
        kv_window_pages=int(window_pages))


def row_config(cfg: TransformerConfig) -> TransformerConfig:
    """The contiguous (per-row cache) twin of a paged config — prefill and
    suffix-prefill run here, then scatter into the pool."""
    return dataclasses.replace(cfg, kv_page_size=0, kv_num_pages=0, kv_window_pages=0)


def window_bound(window: int, chunk: int, page_size: int) -> int:
    """Most window-group pages one live request holds, a layer: the blocks
    that ``window`` keys and the ``chunk`` positions a decode chunk writes can
    lie in, plus one for where the first of them falls in its page."""
    return -(-(window + chunk) // page_size) + 1


def _window_layer_names(cfg: TransformerConfig) -> frozenset:
    return frozenset(f"layer_{i}" for i in cfg.window_layers)


def _in_window_layer(path, names: frozenset) -> bool:
    return bool(names) and str(getattr(path[0], "key", path[0])) in names


def _num_blocks(cfg: TransformerConfig) -> int:
    return cfg.max_seq_len // cfg.kv_page_size


def _donate(argnum: int) -> Tuple[int, ...]:
    """``donate_argnums`` for a program that takes the pool and returns it:
    donating halves peak HBM for the biggest buffer in serving and spares the
    program a copy of it. Asked for on the TPU only: on the CPU the tests call
    these programs with arrays they go on to read."""
    return (argnum,) if jax.default_backend() == "tpu" else ()


def paged_pool_init(params, cfg: TransformerConfig, B: int):
    """The empty page-pool cache pytree: zeros in the shapes one decode
    step's apply gives its cache. The apply is only traced for its shapes:
    run eagerly it was a hundred one-op programs in every set-up."""
    model = decode_model(cfg)

    def cache_of(p):
        return model.apply(
            {"params": p},
            jnp.zeros((B, 1), jnp.int32),
            positions=jnp.zeros((B, 1), jnp.int32),
            cache_idx=jnp.zeros((B,), jnp.int32),
            block_tables=jnp.zeros((B, _num_blocks(cfg)), jnp.int32),
            window_tables=jnp.zeros((B, _num_blocks(cfg)), jnp.int32) if cfg.window_layers else None,
            mutable=["cache"],
        )[1]["cache"]

    return jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype),
                                  jax.eval_shape(cache_of, params))


def snapshot_of(row_cache) -> dict:
    """The prefix-cache snapshot a prefill left in its (packed) row cache:
    ``{conv, ssm}`` stacked over the Mamba layers from the ``snap_*`` leaves,
    the shape ``_paged_gather_fn`` takes it back in."""
    return {dst: row_cache[PACKED][src] for src, dst in SNAPSHOT_LEAVES.items()}


def _page_groups(cfg: TransformerConfig, pool) -> List[list]:
    """The pool's K/V (or latent) leaves by page group, as key paths: the full
    group's first, then the window group's where the model has window layers.
    Scalars (write indices) and recurrent-state leaves belong to neither."""
    windowed = _window_layer_names(cfg)
    groups: List[list] = [[], []] if windowed else [[]]
    for path, leaf in jax.tree_util.tree_flatten_with_path(pool)[0]:
        if leaf.ndim and _leaf_name(path) not in STATE_LEAVES:
            groups[int(_in_window_layer(path, windowed))].append(path)
    return groups


def _paged_admit_fn(cfg: TransformerConfig):
    """Write one finished contiguous row cache into the cache pytree, sample
    the request's first token and write the request's row of the decode
    step's carry. K/V leaves: of each page group the program moves ONE RUNTIME
    RANGE of the row's logical blocks, ``spans[g] = (first, count)``, block
    ``blk`` to page ``ids[blk]`` of every leaf of the group (``write_ids`` for
    the full group, ``window_write_ids`` for the window group's pools: one
    entry per logical block, read inside the range only). The full group's
    range is the prompt's blocks behind the shared prefix, the window group's
    those of its last ``sliding_window`` tokens. A block outside the range is
    neither read from the row nor written anywhere: what the request does not
    own (shared prefix pages, the unallocated tail, the row's padding) costs
    nothing, so the program's time follows the prompt and not ``max_seq_len``;
    a range of no blocks writes nothing. The range is an operand: one
    executable serves every prompt length. Recurrent-state leaves
    (``STATE_LEAVES``) are written whole at the request's ``slot``. Leaves only
    the row has (its snapshot) stay behind. The row arrives packed
    (``models/mamba.pack_state``); ``first_logits`` is the prefill's
    ``[1, vocab]``; ``seed`` is the request's seed as a uint32, from which the
    program makes ``jax.random.PRNGKey(seed)`` itself. ``carry`` is what
    ``_paged_step_fn`` carries from chunk to chunk, ``(tok, lengths, keys)``;
    it comes back with row ``slot`` set to (the first token, ``length`` = the
    prompt's, the key the first token's draw left), so the request can ride a
    chunk launched before its first token has reached the host.

    The pool is DONATED where the backend donates and updated in place (the
    page moves are ``ops/page_handoff.rows_to_pages``: one kernel a page group,
    a DMA a page, the pools aliased in to out; no op copies a pool): the
    caller's binding is dead once the call is made, and it rebinds to the
    returned pool. That holds in the engine because everything that reads or
    writes the pool is launched from one thread, in program order."""
    n_blocks = _num_blocks(cfg)
    ps = cfg.kv_page_size

    def build():
        def run(pool, row_cache, write_ids, slot, first_logits, seed, temp, carry, length, spans,
                window_write_ids=None):
            row_cache = unpack_state(cfg, row_cache)

            moved = {}  # a page leaf's path -> the leaf with the request's blocks in their pages
            for g, (paths, ids) in enumerate(zip(_page_groups(cfg, pool), (write_ids, window_write_ids))):
                first = jnp.clip(spans[g, 0], 0, n_blocks)
                count = jnp.clip(spans[g, 1], 0, n_blocks - first)
                dsts = [_leaf_at(pool, path) for path in paths]
                srcs = [_leaf_at(row_cache, path).astype(dst.dtype) for path, dst in zip(paths, dsts)]
                moved.update(zip(paths, rows_to_pages(srcs, dsts, ids, jnp.stack([first, count]), page_size=ps)))

            def insert(path, dst):
                if path in moved:
                    return moved[path]
                if _leaf_name(path) not in STATE_LEAVES:
                    return dst  # scalar write index: meaningless for pools
                src = _leaf_at(row_cache, path)
                return jax.lax.dynamic_update_slice(
                    dst, src.astype(dst.dtype), (slot,) + (0,) * (dst.ndim - 1))

            new_pool = jax.tree_util.tree_map_with_path(insert, pool)
            key2, sub = jax.random.split(jax.random.PRNGKey(seed))
            tok0 = _sample(first_logits[0], sub, temp)
            tok, lengths, keys = carry
            carry = tok.at[slot].set(tok0), lengths.at[slot].set(length), keys.at[slot].set(key2)
            return new_pool, tok0, carry

        return jax.jit(track_compiles(run, name="paged_admit"),
                       donate_argnums=_donate(0))

    return _lru_get(("paged_admit", cfg), build)


def _paged_gather_fn(cfg: TransformerConfig):
    """Stage the row cache a suffix prefill starts from. K/V leaves: gather
    one request's pages back into a contiguous [1, S, kv, hd] row, write index
    rewound to the shared prefix length; blocks beyond the prefix point at the
    trash page, their garbage is overwritten by the suffix pass before any
    query can attend to it (the ``_rewind_cache`` argument). Recurrent-state
    leaves: the prefix cache's snapshot ``state`` (``snapshot_of``'s shape),
    never the pool's slots, which hold other requests. The row is packed. A
    window layer's row comes from the window group through ``window_table``:
    the blocks the suffix pass can see hold the shared pages, the others trash.

    The whole table is gathered, ``max_seq_len / page_size`` pages a leaf
    whatever the prefix: the program makes a row of that length anyway, and a
    gather of it runs at what the row's bytes cost (0.41 ms at a 16,896-token
    row of 10 leaves, 0.05-0.16 ms at 2,048 tokens: TPU v5e), under what a
    loop over the shared blocks alone costs at short rows."""
    ps = cfg.kv_page_size
    recurrent = mamba_layers(cfg)
    windowed = _window_layer_names(cfg)

    def build():
        def run(pool, block_table, prefix_len, state=None, window_table=None):
            def gather(path, leaf):
                if leaf.ndim == 0:
                    return leaf
                table = window_table if _in_window_layer(path, windowed) else block_table
                pages = leaf[table]  # [n_blocks, ps, kv, hd]
                return pages.reshape((1, pages.shape[0] * ps) + leaf.shape[2:])

            row = jax.tree_util.tree_map_with_path(
                gather, {k: v for k, v in pool.items() if k not in recurrent})
            row = _rewind_cache(row, prefix_len)
            if state is not None:
                row[PACKED] = state
            return row

        return jax.jit(track_compiles(run, name="paged_gather"))

    return _lru_get(("paged_gather", cfg), build)


def _suffix_prefill_fn(cfg: TransformerConfig, T_b: int):
    """One multi-token decode-mode pass over just the SUFFIX of a prompt
    whose prefix pages were served from the prefix cache — the compute
    skip that makes prefix sharing a TTFT win, not only an HBM win.
    Compiled per 16-token suffix bucket; the start position (shared
    prefix length) is a runtime value via the cache's rewound index. A
    recurrent layer starts from the snapshot the row cache holds, stops at
    ``true_total`` and keeps its state at ``snap_total`` (absolute positions)
    for the prefix cache. Row caches cross the boundary packed, both ways."""

    def build():
        model = decode_model(row_config(cfg))

        def run(params, row_cache, suffix_padded, prefix_len, true_total, snap_total=None):
            positions = prefix_len + jnp.arange(T_b)[None, :]
            logits, state = model.apply(
                {"params": params, "cache": unpack_state(cfg, row_cache)},
                suffix_padded,
                positions=positions,
                mutable=_mutable(cfg),
                seq_lens=jnp.reshape(true_total - prefix_len, (1,)),
                snap_lens=(None if snap_total is None
                           else jnp.reshape(jnp.maximum(snap_total - prefix_len, 0), (1,))),
                logit_rows=jnp.reshape(true_total - prefix_len - 1, (1,)),
            )
            first = logits[:, 0]  # [1, vocab], as _prefill_fn's
            out = pack_state(cfg, _rewind_cache(state["cache"], true_total)), first
            return out + _routing(cfg, state, true_total - prefix_len)

        return jax.jit(track_compiles(run, name="paged_suffix_prefill"))

    return _lru_get(("paged_suffix", cfg, T_b), build)


def _paged_step_fn(cfg: TransformerConfig, B: int, C: int):
    """The engine's one hot executable: C single-token steps over all B
    rows, addressing the shared page pool through runtime block tables.
    Everything per-request is runtime data (lengths, tables, temps, keys,
    active mask), so this compiles ONCE per (cfg, B, C) and every admission
    mix reuses it. The cache argument is the POOL (page-count-sized, not
    B-sized), so HBM scales with admitted tokens instead of worst-case rows.
    A model with routed layers returns a sixth result: the chunk's routing,
    summed over its C token-steps, packed (``models/moe.routing_stats``). A
    model with window layers takes the window group's tables as one more
    operand, runtime data like the others."""

    def build():
        model = decode_model(cfg)
        S = cfg.max_seq_len
        routed = bool(cfg.routed_layers)

        def run(params, pool, block_tables, tok, lengths, keys, temps, active, window_tables=None):
            n_active = jnp.sum(active.astype(jnp.int32)) if routed else None

            def step(carry, _):
                pool, tok, lengths, keys = carry
                split = jax.vmap(jax.random.split)(keys)  # [B, 2, 2]
                keys2, subs = split[:, 0], split[:, 1]
                # clamp: a row past its budget (mid-chunk EOS / inactive)
                # scatters into whatever its table maps there — the trash
                # page for unowned blocks — instead of out of bounds
                idx = jnp.minimum(lengths, S - 1)
                logits, state = model.apply(
                    {"params": params, "cache": pool},
                    tok[:, None],
                    positions=idx[:, None],
                    # -1: a freed slot (stale length, all-trash table) writes
                    # the trash page and reads no page at all
                    cache_idx=jnp.where(active, idx, -1),
                    block_tables=block_tables,
                    window_tables=window_tables,
                    mutable=_mutable(cfg),
                )
                nxt = jax.vmap(_sample)(logits[:, -1], subs, temps)
                nxt = jnp.where(active, nxt, 0)
                lengths = lengths + active.astype(jnp.int32)
                return (state["cache"], nxt, lengths, keys2), (nxt,) + _routing(cfg, state, n_active)

            (pool, tok, lengths, keys), ys = jax.lax.scan(
                step, (pool, tok, lengths, keys), None, length=C
            )
            out = pool, tok, lengths, keys, ys[0].swapaxes(0, 1)  # [B, C]
            return out + ((jnp.sum(ys[1], axis=0),) if routed else ())

        fn = jax.jit(track_compiles(run, name="paged_step"),
                     donate_argnums=_donate(1))
        return devperf.instrument(fn, "paged_step")

    return _lru_get(("paged_step", cfg, B, C), build)


# ---------------------------------------------------------------------------
# host-side allocator: free list + refcounts + prefix trie
# ---------------------------------------------------------------------------


class WaitTimedLock:
    """``with`` over a ``threading.Lock`` that sums, in ``wait_ns``, how long
    its acquisitions took while the telemetry registry is on: two clock reads
    around the acquire, no span an acquisition. One thread's account: the
    engine's worker takes its locks through these (the other threads take the
    bare lock), so the sum is the worker's own wait
    (``serving.engine.iteration``'s ``lock_wait_ns``)."""

    __slots__ = ("_lock", "_registry", "wait_ns")

    def __init__(self, lock):
        self._lock = lock
        self._registry = tel.get_telemetry()
        self.wait_ns = 0

    def __enter__(self):
        if not self._registry.enabled:
            self._lock.acquire()
            return
        t0 = time.perf_counter_ns()
        self._lock.acquire()
        self.wait_ns += time.perf_counter_ns() - t0

    def __exit__(self, *exc):
        self._lock.release()


class _PrefixNode:
    """One hash-consed prompt chunk: a trie edge labeled by ``chunk`` (a
    full page of token ids) holding the physical page that stores it. The
    node keeps one RETENTION reference on its page; live requests mapping
    the page add their own."""

    __slots__ = ("chunk", "page", "wpage", "parent", "children", "tick", "state",
                 "state_bytes")

    def __init__(self, chunk: Tuple[int, ...], page: int,
                 parent: Optional["_PrefixNode"]):
        self.chunk = chunk
        self.page = page
        # the window group's page of the same chunk (retained likewise), where
        # a request's window reached over it at registration; TRASH_PAGE: none
        self.wpage = TRASH_PAGE
        self.parent = parent
        self.children: Dict[Tuple[int, ...], "_PrefixNode"] = {}
        self.tick = 0
        # recurrent layers' state after this node's last token (a snapshot,
        # opaque to the allocator), and its bytes against the budget
        self.state = None
        self.state_bytes = 0


@dataclasses.dataclass
class PrefixMatch:
    """What the prefix cache gives one admission. ``pages``: the shared
    leading pages, one reference held for the caller on each. ``state``: the
    recurrent state at ``len(pages) * page_size`` (None: the request needs
    none, or starts from zero). ``snap_blocks``: the block boundary, deeper
    than ``pages``, whose node matched but holds no snapshot: the prefill is
    to emit the state there (0: none wanted). ``window_pages`` (a model with
    window layers): as long as ``pages``, the window group's page of each
    block the suffix pass can still see, one reference held on each, and
    TRASH_PAGE for the blocks behind its horizon."""

    pages: List[int]
    state: object = None
    snap_blocks: int = 0
    window_pages: List[int] = dataclasses.field(default_factory=list)


class PagedKVAllocator:
    """Free-list page allocator with refcounts, prefix hash-consing, and an
    admission watermark (all host-side bookkeeping; the device never sees
    anything but page-id arrays).

    Thread-safe: the engine worker allocates/frees while HTTP threads read
    ``stats()``. Page ``TRASH_PAGE`` is pinned out of circulation forever.
    What the worker calls a rider (``match``, ``alloc``, ``alloc_window``,
    ``free``, ``free_window``, ``register_prefix``) takes the lock through
    ``worker_lock``, whose ``wait_ns`` is how long those calls waited for it.

    ``window_pages`` > 0 adds the WINDOW GROUP (module docstring): a second
    free list and refcounts (``alloc_window`` / ``free_window``), for a model
    whose window layers see ``window`` keys. The trie and the watermark stay
    the full group's; a trie node may hold a page of the window group too.
    """

    def __init__(self, num_pages: int, page_size: int, *,
                 watermark_frac: float = 0.05, state_budget_bytes: int = 0,
                 window_pages: int = 0, window: int = 0):
        if num_pages < 2:
            raise ValueError("num_pages must be >= 2 (page 0 is trash)")
        if window_pages and (window_pages < 2 or window < 1):
            raise ValueError("a window group needs window_pages >= 2 (page 0 is trash) and window >= 1")
        self.window_pages = int(window_pages)
        self.window = int(window)
        self._wfree: List[int] = list(range(self.window_pages - 1, TRASH_PAGE, -1))
        self._wref = [0] * self.window_pages
        if self.window_pages:
            self._wref[TRASH_PAGE] = 1  # pinned
        self._window_released = 0
        self._window_evictions = 0
        self._window_alloc_fail = 0
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        # pages below this stay in reserve: admission defers instead of
        # draining the pool to zero (in-flight decode never waits on alloc
        # because every request reserves its full budget at admit)
        self.watermark = max(1, int((num_pages - 1) * watermark_frac))
        self._lock = threading.Lock()
        self.worker_lock = WaitTimedLock(self._lock)
        self._free: List[int] = list(range(num_pages - 1, TRASH_PAGE, -1))
        self._ref = [0] * num_pages
        self._ref[TRASH_PAGE] = 1  # pinned
        self._root: Dict[Tuple[int, ...], _PrefixNode] = {}
        self._nodes: List[_PrefixNode] = []
        self._tick = 0
        self._prefix_hits = 0
        self._prefix_misses = 0
        self._evictions = 0
        self._alloc_fail = 0
        # snapshots of recurrent state held by trie nodes
        self.state_budget_bytes = int(state_budget_bytes)
        self._state_bytes = 0
        self._state_hits = 0
        self._state_misses = 0
        self._state_evictions = 0

    # -- page lifecycle ----------------------------------------------------

    def alloc(self, n: int, *, reserve: bool = True) -> Optional[List[int]]:
        """Take ``n`` fresh pages (refcount 1 each), evicting LRU prefix
        retentions if the free list runs short. Returns None — admission
        defers — when the pool cannot cover ``n`` plus the watermark
        reserve without touching pages live requests still map."""
        with self.worker_lock:
            floor = self.watermark if reserve else 0
            if len(self._free) < n + floor:
                self._evict_locked(n + floor - len(self._free))
            if len(self._free) < n + floor:
                self._alloc_fail += 1
                tel.counter("serving.kv.alloc_deferred").add(1)
                return None
            pages = [self._free.pop() for _ in range(n)]
            for p in pages:
                self._ref[p] = 1
            return pages

    def incref(self, pages: Sequence[int]) -> None:
        with self._lock:
            for p in pages:
                if p == TRASH_PAGE:
                    continue
                if self._ref[p] <= 0:
                    raise RuntimeError(f"incref on dead page {p}")
                self._ref[p] += 1

    def free(self, pages: Sequence[int]) -> None:
        """Drop one reference per page; pages reaching zero return to the
        free list. Double-frees fail loudly — a silent one would hand the
        same page to two requests and corrupt both caches."""
        with self.worker_lock:
            for p in pages:
                if p == TRASH_PAGE:
                    continue
                if self._ref[p] <= 0:
                    raise RuntimeError(f"double-free of page {p}")
                self._ref[p] -= 1
                if self._ref[p] == 0:
                    self._free.append(p)

    # -- the window group ----------------------------------------------------

    def alloc_window(self, n: int) -> Optional[List[int]]:
        """Take ``n`` fresh pages of the window group (refcount 1 each). When
        the free list runs short the trie gives up window pages no live
        request maps, least recently used first (the nodes keep their full
        pages). None when that is not enough."""
        with self.worker_lock:
            if len(self._wfree) < n:
                self._evict_window_locked(n - len(self._wfree))
            if len(self._wfree) < n:
                self._window_alloc_fail += 1
                return None
            pages = [self._wfree.pop() for _ in range(n)]
            for p in pages:
                self._wref[p] = 1
            return pages

    def free_window(self, pages: Sequence[int], *, released: bool = False) -> None:
        """Drop one reference per window-group page (``free``'s rules).
        ``released``: the pages left a live request's window (counted)."""
        with self.worker_lock:
            n = 0
            for p in pages:
                if p == TRASH_PAGE:
                    continue
                if self._wref[p] <= 0:
                    raise RuntimeError(f"double-free of window page {p}")
                self._wref[p] -= 1
                n += 1
                if self._wref[p] == 0:
                    self._wfree.append(p)
            if released and n:
                self._window_released += n
                tel.counter("serving.kv.window_pages_released").add(n)

    def _evict_window_locked(self, need: int) -> None:
        held = sorted((n for n in self._nodes if n.wpage != TRASH_PAGE and self._wref[n.wpage] == 1),
                      key=lambda n: n.tick)
        for node in held[:need]:
            self._wref[node.wpage] = 0
            self._wfree.append(node.wpage)
            node.wpage = TRASH_PAGE
            self._window_evictions += 1

    def _window_tail(self, n_blocks: int) -> int:
        """First block of a prefix ``n_blocks`` long that a pass starting
        behind it can still see in a window layer."""
        return max(0, n_blocks * self.page_size - self.window + 1) // self.page_size

    # -- prefix hash-consing -----------------------------------------------

    def _chunks(self, tokens: Sequence[int]) -> List[Tuple[int, ...]]:
        ps = self.page_size
        n_full = len(tokens) // ps
        return [tuple(tokens[i * ps:(i + 1) * ps]) for i in range(n_full)]

    def match_prefix(self, tokens: Sequence[int]) -> List[int]:
        """Longest hash-consed prefix of ``tokens`` (full pages only).
        Returns the shared page ids with one reference taken per page for
        the caller (release via ``free`` with the rest of its table)."""
        return self.match(tokens).pages

    def match(self, tokens: Sequence[int], *, max_blocks: Optional[int] = None,
              need_state: bool = False) -> PrefixMatch:
        """``match_prefix`` for an admission: at most ``max_blocks`` pages,
        and with ``need_state`` (the model has recurrent layers) only as deep
        as the deepest matched node that holds a snapshot, because those
        layers can skip the shared tokens from nowhere else. Matched nodes
        past it take no reference; the deepest of them is where the caller's
        prefill should leave a snapshot (``attach_state``). A state hit is an
        admission that starts from a snapshot, a miss one that starts from
        zero whether or not pages matched."""
        with self.worker_lock:
            nodes: List[_PrefixNode] = []
            level = self._root
            for chunk in self._chunks(tokens):
                node = level.get(chunk)
                if node is None:
                    break
                nodes.append(node)
                level = node.children
            if nodes:
                self._prefix_hits += 1
                tel.counter("serving.kv.prefix_hits").add(1)
            else:
                self._prefix_misses += 1
                tel.counter("serving.kv.prefix_misses").add(1)
            if max_blocks is not None:
                nodes = nodes[:max_blocks]
            if self.window_pages:
                # only as deep as the window group still holds the blocks a
                # suffix pass behind the match would see
                while nodes and any(n.wpage == TRASH_PAGE for n in nodes[self._window_tail(len(nodes)):]):
                    nodes.pop()
            for node in nodes:
                self._tick += 1
                node.tick = self._tick
            out = PrefixMatch([])
            if need_state:
                keep = max((i + 1 for i, n in enumerate(nodes)
                            if n.state is not None), default=0)
                if keep < len(nodes):
                    out.snap_blocks = len(nodes)
                nodes = nodes[:keep]
                if keep:
                    out.state = nodes[-1].state
                    self._state_hits += 1
                    tel.counter("serving.state.prefix_hits").add(1)
                else:
                    self._state_misses += 1
                    tel.counter("serving.state.prefix_misses").add(1)
            for node in nodes:
                self._ref[node.page] += 1
                out.pages.append(node.page)
            if self.window_pages:
                tail = self._window_tail(len(nodes))
                for i, node in enumerate(nodes):
                    if i >= tail:
                        self._wref[node.wpage] += 1
                    out.window_pages.append(node.wpage if i >= tail else TRASH_PAGE)
            return out

    def attach_state(self, tokens: Sequence[int], n_blocks: int, state,
                     nbytes: int) -> bool:
        """Give the node ``n_blocks`` chunks down ``tokens`` the recurrent
        state at its boundary. Refused (False) when the node is gone or has
        one already, or when one snapshot exceeds the whole budget; else the
        LRU snapshots of other nodes make room."""
        with self._lock:
            node, level = None, self._root
            for chunk in self._chunks(tokens)[:n_blocks]:
                node = level.get(chunk)
                if node is None:
                    return False
                level = node.children
            if node is None or node.state is not None or nbytes > self.state_budget_bytes:
                return False
            while self._state_bytes + nbytes > self.state_budget_bytes:
                victim = min((n for n in self._nodes if n.state is not None),
                             key=lambda n: n.tick)
                self._drop_state_locked(victim)
            node.state, node.state_bytes = state, int(nbytes)
            self._tick += 1
            node.tick = self._tick  # a snapshot just written is the most recently used
            self._state_bytes += node.state_bytes
            tel.counter("serving.state.snapshots").add(1)
            self._gauge_state_bytes_locked()
            return True

    def _drop_state_locked(self, node: _PrefixNode) -> None:
        self._state_bytes -= node.state_bytes
        node.state, node.state_bytes = None, 0
        self._state_evictions += 1
        tel.counter("serving.state.snapshot_evictions").add(1)
        self._gauge_state_bytes_locked()

    def _gauge_state_bytes_locked(self) -> None:
        store = tsdb.active()
        if store is not None:
            store.record_gauge("serving.state.snapshot_bytes", float(self._state_bytes))

    def register_prefix(self, tokens: Sequence[int],
                        block_ids: Sequence[int],
                        window_ids: Optional[Sequence[int]] = None) -> None:
        """Hash-cons the prompt's full chunks, retaining one reference on
        each newly published page (already-registered chunks just refresh
        their LRU tick — including ones this request matched at admit).
        Only FULL chunks are registered, so a registered page is never a
        write target (see module docstring). ``window_ids``: the request's
        window-group page of each chunk (TRASH_PAGE where its window does not
        reach); a node without one keeps it, retained likewise."""
        with self.worker_lock:
            chunks = self._chunks(tokens)
            level = self._root
            parent: Optional[_PrefixNode] = None
            for i, chunk in enumerate(chunks):
                node = level.get(chunk)
                if node is None:
                    page = block_ids[i]
                    if page == TRASH_PAGE or self._ref[page] <= 0:
                        break  # caller's table disagrees; don't publish junk
                    node = _PrefixNode(chunk, page, parent)
                    self._ref[page] += 1  # retention reference
                    level[chunk] = node
                    self._nodes.append(node)
                if window_ids is not None and node.wpage == TRASH_PAGE:
                    wp = window_ids[i]
                    if wp != TRASH_PAGE and self._wref[wp] > 0:
                        node.wpage = wp
                        self._wref[wp] += 1  # retention reference
                self._tick += 1
                node.tick = self._tick
                parent = node
                level = node.children

    def _evict_locked(self, need: int) -> None:
        """Reclaim up to ``need`` pages by dropping LRU prefix retentions
        whose pages no live request maps (refcount 1 = retention only).
        Inner trie nodes are only evictable once their children are gone —
        eviction order is leaves-first by last-use tick (one pass over the
        nodes and a heap: a parent joins it when its last child goes)."""
        def evictable(node):
            return not node.children and self._ref[node.page] == 1

        heap = [(n.tick, id(n), n) for n in self._nodes if evictable(n)]
        heapq.heapify(heap)
        gone = set()
        reclaimed = 0
        while reclaimed < need and heap:
            victim = heapq.heappop(heap)[2]
            gone.add(id(victim))
            if victim.state is not None:  # a snapshot goes with its node
                self._drop_state_locked(victim)
            level = victim.parent.children if victim.parent else self._root
            level.pop(victim.chunk, None)
            self._ref[victim.page] -= 1
            if self._ref[victim.page] == 0:
                self._free.append(victim.page)
                reclaimed += 1
            if victim.wpage != TRASH_PAGE:  # its window page goes with it
                self._wref[victim.wpage] -= 1
                if self._wref[victim.wpage] == 0:
                    self._wfree.append(victim.wpage)
            self._evictions += 1
            tel.counter("serving.kv.prefix_evictions").add(1)
            if victim.parent is not None and evictable(victim.parent):
                heapq.heappush(heap, (victim.parent.tick, id(victim.parent), victim.parent))
        if gone:
            self._nodes = [n for n in self._nodes if id(n) not in gone]

    # -- introspection ------------------------------------------------------

    def group_pages(self) -> Dict[str, Tuple[int, int]]:
        """(live, free) pages of each page group."""
        with self._lock:
            out = {"full": (self.num_pages - 1 - len(self._free), len(self._free))}
            if self.window_pages:
                out["window"] = (self.window_pages - 1 - len(self._wfree), len(self._wfree))
            return out

    def stats(self) -> dict:
        with self._lock:
            shared = sum(1 for n in self._nodes if self._ref[n.page] > 1)
            window = {} if not self.window_pages else {
                "kv_window_pages_total": self.window_pages - 1,
                "kv_window_pages_free": len(self._wfree),
                "kv_window_pages_live": self.window_pages - 1 - len(self._wfree),
                "kv_window_pages_released": self._window_released,
                "kv_window_prefix_evictions": self._window_evictions,
                "kv_window_alloc_deferred": self._window_alloc_fail,
            }
            return {
                **window,
                "kv_pages_total": self.num_pages - 1,  # trash excluded
                "kv_pages_free": len(self._free),
                "kv_pages_live": self.num_pages - 1 - len(self._free),
                "kv_pages_shared": shared,
                "kv_prefix_nodes": len(self._nodes),
                "kv_watermark_pages": self.watermark,
                "kv_prefix_hits": self._prefix_hits,
                "kv_prefix_misses": self._prefix_misses,
                "kv_prefix_evictions": self._evictions,
                "kv_alloc_deferred": self._alloc_fail,
                "state_snapshots": sum(1 for n in self._nodes if n.state is not None),
                "state_snapshot_bytes": self._state_bytes,
                "state_budget_bytes": self.state_budget_bytes,
                "state_prefix_hits": self._state_hits,
                "state_prefix_misses": self._state_misses,
                "state_snapshot_evictions": self._state_evictions,
            }

    def check_leaks(self) -> dict:
        """Test hook: with no live requests, every non-free page must be
        either trash or a retained prefix page (refcount exactly 1), and the
        snapshots the trie's nodes hold must be the bytes counted, inside the
        budget (``state_leaked`` lists what is not)."""
        with self._lock:
            held = sum(n.state_bytes for n in self._nodes)
            state_leaked = []
            if held != self._state_bytes:
                state_leaked.append(f"nodes hold {held} bytes, {self._state_bytes} counted")
            if self._state_bytes > self.state_budget_bytes:
                state_leaked.append(f"{self._state_bytes} bytes over the budget "
                                    f"{self.state_budget_bytes}")
            state_leaked += [f"node of page {n.page}: state and bytes disagree"
                             for n in self._nodes if (n.state is None) != (n.state_bytes == 0)]
            retained = {n.page for n in self._nodes}
            leaked = [
                p for p in range(1, self.num_pages)
                if self._ref[p] > 0 and (p not in retained or self._ref[p] != 1)
            ]
            free_set = set(self._free)
            double = [p for p in free_set if self._ref[p] != 0]
            accounted = (len(free_set) + len(retained) + 1 == self.num_pages
                         and not (free_set & retained) and not state_leaked)
            if self.window_pages:  # the window group, by the same rules
                wretained = {n.wpage for n in self._nodes if n.wpage != TRASH_PAGE}
                leaked += [("window", p) for p in range(1, self.window_pages)
                           if self._wref[p] > 0 and (p not in wretained or self._wref[p] != 1)]
                wfree = set(self._wfree)
                double += [("window", p) for p in wfree if self._wref[p] != 0]
                accounted = (accounted and len(wfree) + len(wretained) + 1 == self.window_pages
                             and not (wfree & wretained))
            return {"leaked": leaked, "bad_free": double,
                    "state_leaked": state_leaked, "accounted": accounted}
