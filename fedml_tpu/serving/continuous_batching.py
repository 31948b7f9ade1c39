"""Continuous batching: one decode engine over a paged KV cache.

``PagedContinuousBatchingEngine`` is the serving path (``LLMPredictor(paged=True)``
builds it); ``train/llm/generation.generate`` is the plain reference the tests
hold it to, token for token. What the engine does:

- a fixed pool of ``num_slots`` rows is the decode batch, and ONE jitted
  chunked step (``paged_kv._paged_step_fn``: ``chunk`` tokens per dispatch)
  runs for as long as any slot is live: requests join and leave at token
  boundaries without recompiling or restarting anyone else's decode;
- K/V live in a page pool (serving/paged_kv.py): each request reserves
  ``ceil((prompt + budget) / page_size)`` pages at admit, so HBM scales with
  admitted tokens and decode never runs out of pages mid-flight, and requests
  sharing a hash-consed prompt prefix map the same physical pages;
- prefill is disaggregated: each request prefills alone at B=1 through the
  16-token-bucketed executables (``generation._prefill_fn``; on a prefix hit,
  a gather of the shared pages plus one suffix pass), then a jitted admit
  moves the row's blocks the request owns into its private pages (the page
  handoff: ``paged_kv._paged_admit_fn``), samples its first token and
  writes the request's row of the decode step's carry. An admission wave runs
  on the worker's own thread (``_run_wave``): every rider's programs are
  launched without waiting, so the device sees one chain ``prefill_0,
  admit_0, prefill_1, admit_1, ...`` behind the chunk in flight. One thread
  launches everything that touches the pool, so the admit program donates it
  as the decode step does;
- per-row state stays RUNTIME data: slot lengths ride the transformer's
  ``cache_idx``, block tables, temperatures and PRNG keys are per-row arrays,
  and EOS is checked host-side as a chunk's tokens land, so one executable per
  (cfg, B, C) serves every mix of prompt lengths, sampling settings and stop
  tokens;
- THE LOOP RUNS ONE CHUNK AHEAD OF THE HOST (``_loop``): the worker never
  blocks on the device with nothing queued behind the wait. What a chunk
  carries to the next (each row's last token, length and PRNG key) stays on
  the device: chunk n's results are chunk n+1's operands as they are, an
  admission writes its slot's row there, and the host keeps only what it
  owns (block tables, temperatures, who is live: uploaded when they changed)
  and its own arithmetic copy of the lengths. An iteration launches the
  wave's riders, launches chunk n+1 over the carry (riders included), and
  only then, in the order the device finishes them, fetches chunk n's tokens
  and the riders' first tokens and does their bookkeeping while chunk n+1
  runs. The depth is one chunk: a constant of the design;
- a model with recurrent layers (``cfg.has_recurrent_state``) keeps their
  per-slot state in the same cache pytree as the page pool, and its prefix
  hits start from state snapshots the trie holds, at most ``state_snapshots``
  of them (see serving/paged_kv.py's header);
- a model with latent-attention layers keeps ONE latent leaf a layer in the
  pool (``models/mla.py``) where attention layers keep K and V; a model with
  routed expert layers (``models/moe.RoutedMoE``) has its programs hand back
  the routing of each pass packed in one small array, which becomes the
  ``serving.moe.*`` counters and span attributes;
- a model with window layers (``cfg.window_layers``) keeps their K/V in a
  page group of its own beside the full layers' (serving/paged_kv.py's
  header): an admission reserves the full group's pages as above and, in the
  window group, only the blocks of the prompt's last ``sliding_window``
  tokens; from then on every chunk launch slides the request's window table
  (``_slide_windows``): the blocks the chunk will write are mapped, the blocks
  wholly behind the chunk's first horizon go back to the free list while the
  request decodes, so it never holds more than ``window_bound`` window pages.
  Admission defers when EITHER group is short;
- an optional :class:`AdmissionController` gates the front door: submit-time
  token budgets + shed, dequeue-time weighted fair queueing + SLO-pressure
  deferral (serving/admission.py).

Chunking amortizes dispatch and the per-chunk fetch: one device call yields
``chunk`` tokens for every live slot. Who leaves when: a request that ends on
its BUDGET ends at a step the host can count without seeing a token, so its
row is masked out of the first chunk launched after the one that holds its
last token, and it wastes the rest of that last chunk and no more. A request
that ends on EOS is seen when its chunk lands, one chunk late: its reply is
cut at the EOS and delivered then, and its row rides the chunk already
queued, at most ``2 * chunk - 1`` tokens past the EOS. Either way the host
discards the garbage (``serving.wasted_tokens``), frees the request's pages
and points the slot's table at the trash page. Every launched chunk keeps,
host-side, which request held which row at its launch (``_Chunk.rows``):
tokens of a row whose request has ended, failed or been replaced by a new
rider are dropped, never appended to another request. Pages and slots are
handed on only to programs launched AFTER the last chunk that could write
them: with one launching thread and one device queue, program order gives
that (as it gives the donated pool, ``paged_kv._paged_admit_fn``).

Telemetry: TTFT/TPOT histograms, token/request counters, and a ``stats()``
snapshot (slot occupancy, queue depth, page occupancy) that the inference
runner exports as Prometheus gauges and ``/statusz`` fields. Spans
(docs/observability.md, "Serving request lifecycle"): every request leaves
``serving.request.queue`` / ``.admit`` / ``.decode`` records carrying its
``request_id``; the worker loop leaves ``serving.engine.iteration`` with its
children and ``serving.engine.idle``, and says when the chip had nothing queued
and what its own thread was doing then (``_DeviceLedger``:
``serving.device.starved``).
"""

from __future__ import annotations

import collections
import dataclasses
import logging
import threading
import time
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import telemetry as tel
from ..core.telemetry import devperf, trace_context, tsdb
from ..models.mamba import STATE_LEAVES
from ..models.mamba import state_bytes as mamba_state_bytes
from ..models.moe import ROUTING_HEAD
from ..models.transformer import TransformerConfig
from ..train.llm.generation import _leaf_name, _prefill_fn
from ..train.llm.generation import _sample  # noqa: F401 - tests/benchmark_suite plants a fault by patching it here too
from .admission import DEFAULT_TENANT, AdmissionController, AdmissionError
from .admission import REASON_QUEUE_FULL, count_reject
from .paged_kv import (
    TRASH_PAGE,
    PagedKVAllocator,
    WaitTimedLock,
    _page_groups,
    _paged_admit_fn,
    _paged_gather_fn,
    _paged_step_fn,
    _suffix_prefill_fn,
    paged_config,
    paged_pool_init,
    row_config,
    snapshot_of,
    window_bound,
)

log = logging.getLogger(__name__)

#: decode chunks whose expert loads the ``serving.moe.load_imbalance`` gauge sums
MOE_GAUGE_CHUNKS = 64
#: bytes of prefill rows (one contiguous ``[max_seq_len]`` K/V row a rider, allocated when its prefill is
#: LAUNCHED, not when it runs) that may be launched and not yet scattered into pages: riders past that
#: wait for the next iteration. At a 2,048-token row that is more riders than slots; at a 16,896-token row
#: of 5 layers (173 MB) it is 6, where a wave of twenty had the chip out of memory
ROWS_IN_FLIGHT_BYTES = 2 ** 30
#: what the worker's thread is doing, as the starvation ledger names it (``_DeviceLedger``): a closed set.
#: ``no_work``: nothing to launch (the wait of ``serving.engine.idle``, the backpressure sleep); ``collect``:
#: ``_collect_wave`` and the loop's top; ``launch``: ``_stage_prefill``, ``_stage_transfer``, ``_slide_windows`` +
#: ``_device_copy`` + ``.dispatch``; ``land``: ``.sync`` / ``.post`` / ``first_token_wait`` /
#: ``serving.paged.admit`` and what lies between them
NO_WORK, COLLECT, LAUNCH, LAND = STARVED_PHASES = ("no_work", "collect", "launch", "land")


def _gauge(name: str, value: float) -> None:
    store = tsdb.active()
    if store is not None:
        store.record_gauge(name, value)


class _DeviceLedger:
    """When the chip had nothing queued, and what the worker's thread was
    doing then: the worker's own account, kept without a lock (one thread) and
    read without the profiler.

    The worker is the only thread that launches programs, and the chip runs
    them in order: so when one output of the NEWEST launch is ready, the
    device's queue is empty. ``launched(out)`` keeps that output after every
    launch call (an array the engine never donates; a deleted one reads as
    ready). ``mark(phase)`` is called at every phase boundary of the worker
    (where its spans open and close; ``phase`` is what follows, one of
    ``STARVED_PHASES``) and asks ``is_ready()`` once: it does not block,
    launches nothing, fetches nothing. The first time it answers ready a
    starvation interval is open from that instant; it closes when the next
    launch call returns. Every boundary crossed meanwhile cuts a piece, so each
    piece lies in ONE phase: a ``serving.device.starved`` record
    (``phase``, ``first``, and on an interval's first piece ``unseen_ns``: this
    look minus the look before it, which still read busy) and the counters
    ``serving.device.starved_ns`` / ``serving.device.starvations``. There are
    no boundaries inside a launch, so ``launched`` looks once more: if the
    launch before is done when this one returns and no boundary saw it, the
    queue may have emptied and filled again in between; that is an interval
    of one piece of no length, all of it ``unseen_ns``.

    The two bounds: the chip went idle somewhere inside an interval's
    ``unseen_ns`` (if at all, for a piece of no length), so true idle time lies
    between the pieces' sum and that sum plus the ``unseen_ns``; and a launch
    call's return precedes the device's start by the runtime's own latency,
    which no piece holds.

    It also keeps the worker's account of one iteration (the attributes of
    ``serving.engine.iteration``): ``cpu_ns`` of its thread, ``blocked_ns`` in
    the fetches that wait for the chip, ``lock_wait_ns`` over ``locks``,
    ``starved_ns``. With the registry off every call is one flag check."""

    def __init__(self, locks: Sequence[WaitTimedLock]):
        self._registry = tel.get_telemetry()
        self._locks = tuple(locks)
        self.newest = None       # one output of the newest launch (None: nothing launched yet)
        self._phase = NO_WORK
        self._t_busy = None      # the last look that read busy (a launch's return is one)
        self._t_piece = None     # an interval is open: where its next piece starts
        self._first = False
        self._unseen_ns = 0
        self._starved_ns = 0     # the four of the iteration under way
        self._blocked_ns = 0
        self._lock_wait0 = 0
        self._cpu0 = None

    def _queue_empty(self) -> bool:
        out = self.newest
        return out is None or out.is_deleted() or out.is_ready()

    def _cut(self, now: int) -> None:
        first = {"unseen_ns": self._unseen_ns} if self._first else {}
        tel.record_span("serving.device.starved", self._t_piece, now,
                        phase=self._phase, first=self._first, **first)
        tel.counter("serving.device.starved_ns").add(now - self._t_piece)
        if self._first:
            tel.counter("serving.device.starvations").add(1)
        self._starved_ns += now - self._t_piece
        self._t_piece, self._first = now, False

    def mark(self, phase: str) -> None:
        """A phase boundary: ``phase`` is what the worker does from here on."""
        if not self._registry.enabled:
            self._t_piece = self._t_busy = None
            return
        now = time.perf_counter_ns()
        if self._t_piece is not None:
            self._cut(now)
        elif self._queue_empty():
            self._t_piece, self._first = now, True
            self._unseen_ns = now - self._t_busy if self._t_busy is not None else 0
        else:
            self._t_busy = now
        self._phase = phase

    def launched(self, out) -> None:
        """A launch call has returned: the chip has work again. Where no
        interval is open and the launch BEFORE this one is done, the queue may
        have run empty and been filled again inside this call, between two
        boundaries: a piece of no length whose ``unseen_ns`` holds the doubt."""
        unseen = self._t_piece is None and self._queue_empty()
        self.newest = out
        if not self._registry.enabled:
            self._t_piece = self._t_busy = None
            return
        now = time.perf_counter_ns()
        if unseen:
            self._t_piece, self._first = now, True
            self._unseen_ns = now - self._t_busy if self._t_busy is not None else 0
        if self._t_piece is not None:
            self._cut(now)
            self._t_piece = None
        self._t_busy = now

    def before_fetch(self) -> int:
        """A boundary of ``land`` in front of a span that waits for the chip
        (``.sync``, ``first_token_wait``): the worker's CPU time so far."""
        self.mark(LAND)
        return time.thread_time_ns() if self._registry.enabled else 0

    def after_fetch(self, span, cpu0: int) -> None:
        """The boundary behind that span, once it has closed: what of it the
        thread spent off the CPU is time blocked on the chip (the copy to NumPy
        is ``cpu_ns``'s, so ``cpu_ns + blocked_ns`` never passes the
        iteration's wall time)."""
        if span.duration_ns is not None and self._cpu0 is not None:
            self._blocked_ns += max(0, span.duration_ns - (time.thread_time_ns() - cpu0))
        self.mark(LAND)

    def begin_iteration(self) -> None:
        """Inside the iteration's span, before anything else: what follows is ``_collect_wave``."""
        self.mark(COLLECT)
        if not self._registry.enabled:
            self._cpu0 = None
            return
        self._starved_ns = self._blocked_ns = 0
        self._lock_wait0 = sum(k.wait_ns for k in self._locks)
        self._cpu0 = time.thread_time_ns()

    def end_iteration(self, attrs: Optional[dict]) -> None:
        """The iteration's account onto its span; what follows is the loop's top."""
        if attrs is not None and self._cpu0 is not None:
            attrs.update(cpu_ns=time.thread_time_ns() - self._cpu0, blocked_ns=self._blocked_ns,
                         lock_wait_ns=sum(k.wait_ns for k in self._locks) - self._lock_wait0)
            self.mark(COLLECT)
            attrs["starved_ns"] = self._starved_ns


class RequestHandle:
    """Future for one submitted request. ``result()`` blocks for the full
    token list; ``text`` is filled when the engine has a tokenizer."""

    def __init__(self, request_id: Optional[str] = None):
        self._ev = threading.Event()
        self._tokens: Optional[List[int]] = None
        self._exc: Optional[BaseException] = None
        self.text: Optional[str] = None
        # the caller's, else the thread's active trace context's, else minted
        self.request_id: str = request_id or trace_context.request_id()
        self.queue_wait_s: Optional[float] = None  # enqueued -> popped for admission
        self.ttft_s: Optional[float] = None  # enqueued -> first token on the host
        self.tpot_s: Optional[float] = None

    def done(self) -> bool:
        return self._ev.is_set()

    def result(self, timeout: Optional[float] = None) -> List[int]:
        if not self._ev.wait(timeout=timeout):
            raise TimeoutError("continuous-batching request timed out")
        if self._exc is not None:
            raise self._exc
        assert self._tokens is not None
        return self._tokens

    def _finish(self, tokens: List[int]) -> None:
        self._tokens = tokens
        self._ev.set()

    def _fail(self, exc: BaseException) -> None:
        self._exc = exc
        self._ev.set()


@dataclasses.dataclass
class _Pending:
    prompt: List[int]
    max_new: int
    temperature: float
    seed: int
    eos_ids: Optional[Tuple[int, ...]]
    handle: RequestHandle
    request_id: str
    t_submit_ns: int  # perf_counter_ns readings: the request's spans are
    t_pop_ns: int = 0  # recorded from them (tel.record_span) by the worker
    queue_depth: int = 0  # requests ahead of this one when it was enqueued
    tenant: str = "default"
    wfq_tag: float = 0.0  # weighted-fair-queueing virtual finish tag


@dataclasses.dataclass
class _Active:
    pending: _Pending
    budget: int  # max_new clamped to max_seq_len - prompt at admit
    tokens: List[int] = dataclasses.field(default_factory=list)  # empty until the first token lands
    t_first_ns: int = 0
    generated: int = 0  # device tokens LAUNCHED for it (the first, + chunk a chunk), kept OR discarded


@dataclasses.dataclass
class _AdmitWork:
    """One request moving through an admission: prefill -> transfer (both
    launched by its wave) -> admit (its first token landed, behind the next
    chunk's launch). Created by ``_collect_wave`` holding its slot + page
    reservations; once ``launched`` its slot's table holds the pages."""

    item: _Pending
    slot: int
    budget: int
    n_shared: int             # leading blocks served from the prefix cache
    shared_pages: List[int]   # one reference held per page
    private_pages: List[int]  # one reference held per page
    window_shared: List[int] = dataclasses.field(default_factory=list)   # window group: the match's, as long as
    window_private: List[int] = dataclasses.field(default_factory=list)  # shared_pages (TRASH behind the horizon); own
    state: object = None      # recurrent layers start from this snapshot (None: from zero)
    snap_blocks: int = 0      # block boundary whose trie node wants this prefill's state
    row_cache: object = None
    first: object = None      # [1, vocab] logits the first token is sampled from
    tok0: object = None       # on the device from the transfer until _stage_admit fetches it
    routing: object = None    # likewise: the prefill's packed routing (models with routed layers)
    prefill_span: object = None  # gets the routing's attributes once they are on the host
    launched: bool = False    # its row is in the carry, its slot and table published


@dataclasses.dataclass
class _Chunk:
    """One launched decode chunk until its tokens are on the host."""

    rows: List[Optional[_Active]]  # who held which row at the launch (None: masked out)
    toks: object                   # [B, C], on its way to the host
    routing: object                # the chunk's packed routing (models with routed layers), likewise
    span_attrs: Optional[dict]     # of the ``serving.cb.chunk`` span that launched it: gets the routing's
    t_launch_ns: int


class PagedContinuousBatchingEngine:
    """Continuous-batching decode engine over a paged KV cache (see the
    module docstring and serving/paged_kv.py).

    ``submit()`` is thread-safe and non-blocking (a request is admitted when
    a slot and its pages are free: FIFO, or weighted-fair under an
    :class:`AdmissionController`); ``generate()`` is the blocking
    convenience. One engine owns one page pool and one worker thread; model
    params are shared, read-only."""

    def __init__(
        self,
        params,
        cfg: TransformerConfig,
        *,
        num_slots: int = 8,
        chunk: int = 8,
        page_size: int = 16,
        num_pages: Optional[int] = None,
        watermark_frac: float = 0.05,
        max_queue: int = 4096,
        admission: Optional[AdmissionController] = None,
        state_snapshots: int = 8,
        num_window_pages: Optional[int] = None,
    ):
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        if chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {chunk}")
        base = row_config(cfg)
        if num_pages is None:
            # default: room for every slot at max_seq_len (+trash);
            # deployments shrink this to realize the HBM win
            num_pages = num_slots * (base.max_seq_len // page_size) + 1
        # window layers: a page group of their own, a request's share of it bounded
        self._window = base.sliding_window if base.window_layers else 0
        self._win_bound = window_bound(self._window, int(chunk), int(page_size)) if self._window else 0
        if self._window and num_window_pages is None:
            # every slot at its bound, one bound more for what the prefix cache retains, the trash page
            num_window_pages = (num_slots + 1) * self._win_bound + 1
        self._paged_cfg = paged_config(
            base, page_size=page_size, num_pages=num_pages, window_pages=int(num_window_pages or 0))
        self._ps = int(page_size)
        self._n_blocks = base.max_seq_len // self._ps
        self._stateful = base.has_recurrent_state
        self._state_bytes = mamba_state_bytes(base) if self._stateful else 0
        # bytes one live token holds in the latent layers' pages
        self._latent_token_bytes = (base.latent_layers * base.latent_width
                                    * jnp.dtype(base.dtype).itemsize)
        # routed layers: the routing's facts (``ROUTING_HEAD``) and the pairs by
        # held expert since the engine started; the last chunks' loads for the gauge
        self._routed = bool(base.routed_layers)
        self._moe_totals = np.zeros((len(ROUTING_HEAD),), np.int64)
        self._moe_load = np.zeros((base.moe_held_experts or base.moe_routed_experts,), np.int64)
        self._moe_recent: "collections.deque" = collections.deque(maxlen=MOE_GAUGE_CHUNKS)
        self._alloc = PagedKVAllocator(
            num_pages, page_size, watermark_frac=watermark_frac,
            state_budget_bytes=int(state_snapshots) * self._state_bytes,
            window_pages=int(num_window_pages or 0), window=self._window)
        self._admit_deferred = {"full": 0, "window": 0}
        self._admission = admission
        self._tenant_ttft: dict = {}
        self._params = params
        self._cfg = base
        self._B = int(num_slots)
        self._C = int(chunk)
        self._max_queue = int(max_queue)

        self._cache = paged_pool_init(self._params, self._paged_cfg, self._B)
        token_bytes = sum(int(np.prod(leaf.shape[2:])) * leaf.dtype.itemsize
                          for path, leaf in jax.tree_util.tree_flatten_with_path(self._cache)[0]
                          if leaf.ndim >= 3 and _leaf_name(path) not in STATE_LEAVES)
        self._max_riders = max(2, ROWS_IN_FLIGHT_BYTES // max(1, token_bytes * base.max_seq_len))
        # K/V (or latent) leaves a page group: what one logical block costs the page handoff in page writes
        self._group_leaves = [len(paths) for paths in _page_groups(self._paged_cfg, self._cache)]

        # what a chunk hands the next, (tok, lengths, keys): on the device from
        # chunk to chunk, a row of it written by that slot's admission
        self._carry = (jnp.zeros((self._B,), jnp.int32), jnp.zeros((self._B,), jnp.int32),
                       jnp.tile(jnp.asarray(jax.random.PRNGKey(0), jnp.uint32), (self._B, 1)))
        # per-slot state the HOST owns (changed at an admission or a release,
        # uploaded when it changed: ``_device_copy``) and its own arithmetic
        # copy of the carried lengths (stats and the chunk span's ``pages``)
        self._slots: List[Optional[_Active]] = [None] * self._B
        self._lengths = np.zeros((self._B,), np.int32)
        self._temps = np.zeros((self._B,), np.float32)
        self._tables = np.full((self._B, self._n_blocks), TRASH_PAGE, np.int32)
        # the window group's tables (same logical blocks) and, a slot, the mapped blocks [lo, hi)
        self._wtables = np.full((self._B, self._n_blocks), TRASH_PAGE, np.int32) if self._window else None
        self._wspan = np.zeros((self._B, 2), np.int64)
        self._uploaded: dict = {}  # name -> (the host array as uploaded, its device copy)
        # the worker's own: the chunk launched and not yet fetched, the riders
        # launched whose first tokens are still on the device, when the last
        # chunk landed
        self._inflight: Optional[_Chunk] = None
        self._riders: List[_AdmitWork] = []
        self._t_landed_ns = 0

        self._lock = threading.Lock()
        self._work = threading.Condition(self._lock)
        # the engine lock as the WORKER takes it, and the worker's account of the chip's queue and of itself
        self._wlock = WaitTimedLock(self._lock)
        self._ledger = _DeviceLedger((self._wlock, self._alloc.worker_lock))
        self._queue: "collections.deque[_Pending]" = collections.deque()
        self._stopping = False
        self._requests_done = 0
        self._tokens_out = 0  # KEPT tokens (post-EOS/budget truncation)
        self._worker = threading.Thread(
            target=self._loop, name="cb-engine", daemon=True
        )
        self._worker.start()

    # -- public API --------------------------------------------------------

    def submit(
        self,
        prompt: Sequence[int],
        max_new_tokens: int,
        *,
        temperature: float = 0.0,
        seed: int = 0,
        eos_id=None,
        tenant: str = DEFAULT_TENANT,
        request_id: Optional[str] = None,
    ) -> RequestHandle:
        handle = RequestHandle(request_id)
        prompt = [int(t) for t in prompt]
        if self._admission is not None:
            reason = self._admission.check(
                tenant, len(prompt) + int(max_new_tokens))
            if reason is not None:
                handle._fail(AdmissionError(tenant, reason))
                return handle
        eos_ids: Optional[Tuple[int, ...]] = None
        if eos_id is not None:
            eos_ids = (
                tuple(int(e) for e in eos_id)
                if isinstance(eos_id, (list, tuple))
                else (int(eos_id),)
            )
        if len(prompt) < 1:
            handle._fail(ValueError("prompt must contain at least one token"))
            return handle
        if max_new_tokens < 1:
            handle._fail(
                ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
            )
            return handle
        if len(prompt) + 1 > self._cfg.max_seq_len:
            handle._fail(
                ValueError(
                    f"prompt {len(prompt)} leaves no decode room in "
                    f"max_seq_len {self._cfg.max_seq_len}"
                )
            )
            return handle
        item = _Pending(
            prompt, int(max_new_tokens), float(temperature), int(seed),
            eos_ids, handle, handle.request_id, time.perf_counter_ns(),
            tenant=str(tenant),
        )
        with self._work:
            if self._stopping:
                handle._fail(RuntimeError("engine is shutting down"))
                return handle
            if len(self._queue) >= self._max_queue:
                count_reject(item.tenant, REASON_QUEUE_FULL)
                handle._fail(AdmissionError(item.tenant, REASON_QUEUE_FULL))
                return handle
            if self._admission is not None:
                item.wfq_tag = self._admission.stamp(
                    item.tenant, len(item.prompt) + item.max_new)
            item.queue_depth = len(self._queue)
            self._queue.append(item)
            tel.counter("serving.cb.requests").add(1)
            self._work.notify()
        return handle

    def generate(
        self,
        prompt: Sequence[int],
        max_new_tokens: int,
        *,
        temperature: float = 0.0,
        seed: int = 0,
        eos_id=None,
        timeout: Optional[float] = 600.0,
        tenant: str = DEFAULT_TENANT,
    ) -> List[int]:
        return self.submit(
            prompt, max_new_tokens, temperature=temperature, seed=seed,
            eos_id=eos_id, tenant=tenant,
        ).result(timeout=timeout)

    def stats(self) -> dict:
        """Gauge snapshot for /metrics and /statusz (cheap; lock-guarded)."""
        with self._lock:
            active = sum(1 for s in self._slots if s is not None)
            live = self._live_tokens_locked()
            out = {
                "slots_total": self._B,
                "slots_active": active,
                "slot_occupancy": active / self._B,
                "queue_depth": len(self._queue),
                "chunk": self._C,
                "requests_done": self._requests_done,
                "tokens_out": self._tokens_out,
            }
        a = self._alloc.stats()
        pages_used = a["kv_pages_total"] - a["kv_pages_free"]
        out.update(a)
        out.update({
            "kv_page_size": self._ps,
            "kv_pages_in_use": pages_used,
            "kv_tokens_live": live,
            # pages per live token (multiply by page bytes for bytes/token)
            "kv_pages_per_token": pages_used / live if live else 0.0,
        })
        if self._latent_token_bytes:
            out["kv_latent_bytes_live"] = live * self._latent_token_bytes
        out["kv_admit_deferred_full"] = self._admit_deferred["full"]
        if self._window:
            with self._lock:
                live_rows = [i for i, s in enumerate(self._slots) if s is not None]
                lens = [int(self._lengths[i]) for i in live_rows]
                held = int(np.count_nonzero(self._wtables[live_rows] != TRASH_PAGE))
            out.update(kv_admit_deferred_window=self._admit_deferred["window"],
                       kv_window_bound_pages=self._win_bound,
                       # blocks the live requests map in the window group (the trie's retentions apart)
                       kv_window_pages_held=held,
                       # pages the live requests' window layers would hold with no horizon
                       kv_window_pages_unbounded=int(sum(-(-n // self._ps) for n in lens)))
        if self._routed:
            with self._lock:
                out.update({f"moe_{k}": int(v) for k, v in zip(ROUTING_HEAD, self._moe_totals)},
                           moe_expert_load=[int(x) for x in self._moe_load])
        if self._admission is not None:
            out["admission"] = self._admission.stats()
        return out

    def _live_tokens_locked(self) -> int:
        """Tokens the live slots hold in the cache (caller holds the engine lock)."""
        return int(sum(int(self._lengths[i]) for i, s in enumerate(self._slots) if s is not None))

    def prom_gauges(self) -> list:
        """(name, labels, value) ride-along triples for /metrics."""
        out = []
        st = self._alloc.stats()
        out.append(("serving_kv_pages", {"state": "free"},
                    float(st["kv_pages_free"])))
        out.append(("serving_kv_pages", {"state": "used"},
                    float(st["kv_pages_total"] - st["kv_pages_free"])))
        out.append(("serving_kv_pages", {"state": "watermark"},
                    float(st["kv_watermark_pages"])))
        out.append(("serving_kv_prefix_nodes", None,
                    float(st["kv_prefix_nodes"])))
        if self._stateful:
            out.append(("serving_state_snapshot_bytes", None,
                        float(st["state_snapshot_bytes"])))
        if self._latent_token_bytes:
            with self._lock:
                live = self._live_tokens_locked()
            out.append(("serving_kv_latent_bytes_live", None, float(live * self._latent_token_bytes)))
        if self._routed:
            with self._lock:
                recent = np.sum(self._moe_recent, axis=0) if self._moe_recent else np.zeros((1,))
            if recent.sum() > 0:
                out.append(("serving_moe_load_imbalance", None, float(recent.max() / recent.mean())))
        with self._lock:
            tenants = [(t, sorted(dq)) for t, dq in self._tenant_ttft.items()
                       if dq]
        for t, xs in tenants:
            p99 = xs[min(len(xs) - 1, int(0.99 * len(xs)))]
            out.append(("serving_tenant_ttft_p99_seconds", {"tenant": t},  # fedlint: disable=label-cardinality tenant set is bounded by the configured admission table, not the client population
                        float(p99)))
        if self._admission is not None:
            out.extend(self._admission.prom_gauges())
        return out

    def shutdown(self, timeout: float = 10.0) -> None:
        """Stop the worker; queued and in-flight requests fail fast (the
        callers' futures unblock) rather than hang."""
        with self._work:
            self._stopping = True
            self._work.notify()
        self._worker.join(timeout=timeout)

    # -- worker ------------------------------------------------------------

    def _idle_locked(self) -> bool:
        return (not self._stopping and not self._queue and self._inflight is None
                and all(s is None for s in self._slots))

    def _fail_live_locked(self, err: BaseException) -> None:
        """Fail every request that holds a slot (riders launched and not yet
        landed hold theirs), free their pages and forget what is in flight
        (caller holds the engine lock)."""
        self._inflight = None
        self._riders = []
        for i, s in enumerate(self._slots):
            if s is not None:
                s.pending.handle._fail(err)
                self._release_slot(i)
                self._slots[i] = None

    def _loop(self) -> None:
        """One iteration: launch the wave's riders (``_admit_all``), launch
        the next chunk over the carry, riders included (``_step_chunk``), and
        only then fetch what the device finishes first: the chunk that was in
        flight, then the riders' first tokens (``_land_riders``), while the
        chunk just launched runs. When nothing is in flight the iteration
        launches and goes round; when nothing is left to launch the chunk still
        in flight is landed on its own."""
        while True:
            with self._work:
                if self._idle_locked():
                    self._ledger.mark(NO_WORK)
                    with tel.span("serving.engine.idle"):
                        while self._idle_locked():
                            self._work.wait()
                    self._ledger.mark(COLLECT)
                if self._stopping:
                    err = RuntimeError("engine is shutting down")
                    for item in self._queue:
                        item.handle._fail(err)
                    self._queue.clear()
                    self._fail_live_locked(err)
                    return
                n_active = sum(1 for s in self._slots if s is not None)
                n_queued = len(self._queue)
            try:
                with tel.span("serving.engine.iteration", slots=n_active,
                              queue_depth=n_queued) as iteration:
                    self._ledger.begin_iteration()
                    try:
                        self._admit_all()  # fedlint: disable=interproc-host-sync admission copies prompts host->device once per request, not per token, and waits for nothing
                        if any(s is not None and s.generated < s.budget for s in self._slots):
                            self._step_chunk()  # fedlint: disable=interproc-host-sync one bounded fetch per decode chunk, behind the next chunk's launch: tokens must reach the host to stream to callers
                        elif self._inflight is not None:
                            chunk, self._inflight = self._inflight, None
                            self._land_chunk(chunk)  # fedlint: disable=interproc-host-sync the last chunk's fetch: nothing is left to launch ahead of it
                        self._land_riders()  # fedlint: disable=interproc-host-sync one fetch per admission, behind the chunk that carries the rider
                    finally:
                        self._ledger.end_iteration(getattr(iteration, "attrs", None))
            except Exception as e:  # noqa: BLE001 - engine thread boundary:
                # fail every rider (of the chunk at fault and of the one queued
                # behind it) rather than die silently with their futures
                # hanging; next iteration serves fresh requests
                log.exception("continuous-batching worker step failed")
                with self._wlock:
                    self._fail_live_locked(e)

    def _admit_all(self) -> None:
        while True:
            deferred = sum(self._admit_deferred.values())
            with tel.span("serving.engine.collect_wave") as collect:
                wave = self._collect_wave()
                attrs = getattr(collect, "attrs", None)
                if attrs is not None:
                    attrs.update(n=len(wave), deferred=sum(self._admit_deferred.values()) - deferred)
            if not wave:
                with self._wlock:
                    held_back = (bool(self._queue) and self._inflight is None
                                 and all(s is None for s in self._slots))
                if held_back:
                    # every queued tenant is deferred (or the pool is
                    # draining) and nothing is in flight: don't spin the
                    # worker loop hot while backpressure holds
                    self._ledger.mark(NO_WORK)
                    time.sleep(0.005)  # fedlint: disable=bare-sleep backpressure idle, not a retry
                    self._ledger.mark(COLLECT)
                return
            with tel.span("serving.paged.admit_wave", n=len(wave)):
                self._run_wave(wave)
            self._ledger.mark(COLLECT)

    def _pick_locked(self) -> Optional[_Pending]:
        """Next request to admit (caller holds the engine lock): FIFO
        without a controller, else the smallest WFQ virtual-finish tag
        among tenants that are not deferred. O(queue) per admission — the
        deep 10k-stream backlog lives in the bench driver, not here."""
        if self._admission is None:
            return self._queue.popleft() if self._queue else None
        best = None
        eligible_cache: dict = {}
        for item in self._queue:
            ok = eligible_cache.get(item.tenant)
            if ok is None:
                ok = self._admission.eligible(item.tenant)
                eligible_cache[item.tenant] = ok
            if ok and (best is None or item.wfq_tag < best.wfq_tag):
                best = item
        if best is None:
            return None
        self._queue.remove(best)
        self._admission.on_dequeue(best.wfq_tag)
        return best

    def _collect_wave(self) -> List[_AdmitWork]:
        cfg = self._cfg
        wave: List[_AdmitWork] = []
        taken: set = set()
        while True:
            with self._wlock:
                free = next((i for i, s in enumerate(self._slots)
                             if s is None and i not in taken), None)
                if free is None or not self._queue or len(wave) + len(self._riders) >= self._max_riders:
                    return wave
                item = self._pick_locked()
            if item is None:  # every queued tenant is deferred right now
                return wave
            item.t_pop_ns = time.perf_counter_ns()  # a deferred item is popped again
            P = len(item.prompt)
            # clamp to capacity: decode writes land at P..P+budget-2 (the
            # first token is sampled from prefill logits, never written
            # ahead), so budget = S - P keeps every KEPT token's write inside
            # the pages reserved below
            budget = min(item.max_new, cfg.max_seq_len - P)
            n_req = -(-(P + budget) // self._ps)
            # never map the block holding the prompt's LAST token from the
            # prefix cache: the suffix pass needs >= 1 real token for the
            # first-logits read, and when P is page-aligned decode writes
            # begin in exactly that block (shared pages are never written).
            # With recurrent layers the match is also cut to its deepest
            # snapshot: pages past it would be recomputed anyway.
            match = self._alloc.match(item.prompt, max_blocks=(P - 1) // self._ps,
                                      need_state=self._stateful)
            shared = match.pages
            private = self._alloc.alloc(n_req - len(shared))
            short = "full" if private is None else None
            window_private: List[int] = []
            if private is not None and self._window:
                # the window group: the blocks of the prompt's last ``window`` tokens that the match
                # does not bring; and never more live requests than it holds bounds for
                first_w = max(max(0, P - self._window + 1) // self._ps, len(shared))
                with self._wlock:
                    live = sum(1 for s in self._slots if s is not None) + len(wave)
                window_private = None
                if (live + 1) * self._win_bound <= self._alloc.window_pages - 1:
                    window_private = self._alloc.alloc_window(-(-P // self._ps) - first_w)
                if window_private is None:
                    self._alloc.free(private)
                    short = "window"
            if short is not None:
                self._admit_deferred[short] += 1
                if short == "window":
                    tel.counter("serving.kv.admit_deferred_window").add(1)
                else:
                    tel.counter("serving.kv.admit_deferred_full").add(1)
                self._alloc.free(shared)
                self._alloc.free_window(match.window_pages)
                with self._wlock:
                    busy = any(s is not None for s in self._slots)
                    if busy or wave:
                        # pages free as in-flight requests finish: defer
                        self._queue.appendleft(item)
                        return wave
                # nothing in flight, nothing admitted, eviction already
                # tried: this request can never fit — fail it, not the pool
                item.handle._fail(RuntimeError(
                    f"prompt {P} + budget {budget} needs "
                    f"{n_req - len(shared)} KV pages; the {short} page group cannot free "
                    "enough (raise num_pages or lower max_new_tokens)"))
                continue
            wave.append(_AdmitWork(item, free, budget, len(shared),
                                   shared, private, match.window_pages, window_private,
                                   match.state, match.snap_blocks))
            taken.add(free)

    def _run_wave(self, wave: List[_AdmitWork]) -> None:
        """Launch the wave's riders on this (the worker's) thread, waiting for
        none: each rider's programs (``_stage_prefill``, ``_stage_transfer``)
        queue behind the rider's before it. Their first tokens are fetched
        once the chunk that carries them is launched (``_land_riders``).

        A rider whose launch raises is failed alone: its pages go back, riders
        already decoding keep decoding, riders behind it are launched. The
        exception is a call that raised AFTER it consumed the donated pool: no
        rider can be served from a deleted pool, so the wave's unlaunched
        riders are failed and the error goes on to ``_loop``'s boundary, which
        fails every rider that holds a slot."""
        for w in wave:
            if self._try_stage(self._launch, w, wave):
                self._riders.append(w)

    def _land_riders(self) -> None:
        """Fetch the launched riders' first tokens, in launch order, and do
        their bookkeeping (``_stage_admit``): behind the chunk that carries
        them, so the chip has that chunk to run while the host waits here. A
        rider whose fetch or bookkeeping raises is failed alone; its row is out
        of the next chunk and its tokens of the one in flight reach nobody."""
        riders, self._riders = self._riders, []
        for w in riders:
            self._try_stage(self._stage_admit, w, riders)

    def _try_stage(self, stage, w: _AdmitWork, wave: List[_AdmitWork]) -> bool:
        """Run one stage of rider ``w``; on an exception fail ``w`` (False), or
        the wave's unlaunched riders and re-raise if the pool went with it."""
        try:
            stage(w)
            return True
        except Exception as e:  # noqa: BLE001 - one rider's failure stays that rider's
            log.exception("paged admission of request %s failed", w.item.request_id)
            if not any(x.is_deleted() for x in jax.tree_util.tree_leaves(self._cache)):
                self._fail_rider(w, e)
                return False
            for r in wave:
                if not r.launched:  # a launched rider holds a slot: _loop's boundary fails it
                    self._fail_rider(r, e)
            raise

    def _fail_rider(self, r: _AdmitWork, e: BaseException) -> None:
        if r.item.handle.done():
            return
        if r.launched:  # its slot's table holds its pages
            self._release_slot(r.slot)
            with self._wlock:
                self._slots[r.slot] = None
        else:
            self._alloc.free(r.shared_pages + r.private_pages)
            self._alloc.free_window(r.window_shared + r.window_private)
        r.item.handle._fail(e)

    def _launch(self, w: _AdmitWork) -> None:
        self._ledger.mark(LAUNCH)
        self._stage_prefill(w)
        self._ledger.mark(LAUNCH)
        self._stage_transfer(w)

    def _stage_prefill(self, w: _AdmitWork) -> None:
        """Stage 1, launched and not waited for: produce a contiguous row
        cache + first-token logits — a full bucketed prefill on a prefix MISS,
        or gather-shared-pages + one suffix pass on a HIT (the prefix compute
        skip). Operands cross as NumPy values: no one-op program is run to
        build them."""
        cfg = self._cfg
        item = w.item
        P = len(item.prompt)
        prefix_len = w.n_shared * self._ps
        # where a recurrent layer also keeps its state for the prefix cache; a
        # dense model's programs take neither it nor the snapshot below
        snap = np.int32(w.snap_blocks * self._ps) if self._stateful else None
        attrs = {"state_hit": w.state is not None} if self._stateful else {}
        if self._window:  # key positions the pass reads, a layer of each kind
            seen = np.arange(prefix_len + 1, P + 1, dtype=np.int64)
            attrs.update(kv_tokens_full=int(seen.sum()), kv_tokens_window=int(np.minimum(seen, self._window).sum()))
        if w.n_shared:  # a hit: the shared pages its suffix pass reads (a window layer's: those the match brought)
            seen = [w.n_shared, sum(1 for page in w.window_shared if page != TRASH_PAGE)]
            attrs["blocks_gathered"] = sum(n * leaves for n, leaves in zip(seen, self._group_leaves))
        with tel.span("serving.cb.prefill", request_id=item.request_id,
                      prompt_len=P, shared=prefix_len, **attrs) as w.prefill_span:
            suffix = item.prompt[prefix_len:]
            T_b = min(-(-len(suffix) // 16) * 16, cfg.max_seq_len - prefix_len)
            ids = np.zeros((1, T_b), np.int32)
            ids[0, :len(suffix)] = suffix
            if w.n_shared == 0:
                out = _prefill_fn(cfg, 1, T_b)(self._params, ids, np.int32(P), snap)
            else:
                table = np.full((self._n_blocks,), TRASH_PAGE, np.int32)
                table[:w.n_shared] = w.shared_pages
                more = ()
                if self._window:
                    wtable = np.full((self._n_blocks,), TRASH_PAGE, np.int32)
                    wtable[:w.n_shared] = w.window_shared
                    more = (wtable,)
                row_cache = _paged_gather_fn(self._paged_cfg)(
                    self._cache, table, np.int32(prefix_len), w.state, *more)
                self._ledger.launched(jax.tree_util.tree_leaves(row_cache)[0])
                out = _suffix_prefill_fn(self._paged_cfg, T_b)(
                    self._params, row_cache, ids, np.int32(prefix_len),
                    np.int32(P), snap)
            w.row_cache, w.first = out[:2]
            self._ledger.launched(w.first)
            if self._routed:
                w.routing = out[2]
                w.routing.copy_to_host_async()

    def _stage_transfer(self, w: _AdmitWork) -> None:
        """Stage 2, launched and not waited for: move the row's blocks the
        request OWNS into its private pages, one runtime range of logical blocks
        a page group (the full group: the prompt's blocks behind the shared
        ones; the window group: the blocks of the prompt's last ``window``
        tokens that the match did not bring), write its recurrent state at its
        slot, sample the first token and write the slot's row of the carry.
        Blocks outside the ranges (shared pages, the row's tail) are neither
        read nor written. This is the page handoff, the only stage that writes
        the decode pool; the pool is donated to it. Once it is launched the
        rider's row can ride a chunk, so the host's side of the row is published
        here: block table, temperature, length, and the slot (its reply still
        empty). The span says how far the handoff went: pages written by group
        (``blocks_full``, ``blocks_window``) beside the row's own
        (``blocks_row``: every block of every leaf)."""
        item = w.item
        b = w.slot
        P = len(item.prompt)
        first_blk = w.n_shared
        last_blk = -(-P // self._ps)  # exclusive: block of the last token
        spans = [(first_blk, last_blk - first_blk)]
        if self._window:
            spans.append((last_blk - len(w.window_private), len(w.window_private)))
        written = [n * leaves for (_, n), leaves in zip(spans, self._group_leaves)]
        attrs = {"blocks_full": written[0], "blocks_row": self._n_blocks * sum(self._group_leaves)}
        if self._window:
            attrs["blocks_window"] = written[1]
        with tel.span("serving.paged.transfer", request_id=item.request_id, **attrs):
            write_ids = np.full((self._n_blocks,), TRASH_PAGE, np.int32)
            write_ids[first_blk:last_blk] = w.private_pages[:last_blk - first_blk]
            more = ()
            if self._window:
                first_w = spans[1][0]
                window_ids = np.full((self._n_blocks,), TRASH_PAGE, np.int32)
                window_ids[first_w:last_blk] = w.window_private
                more = (window_ids,)
                self._wtables[b, :] = window_ids
                self._wtables[b, :w.n_shared] = w.window_shared
                held = np.flatnonzero(self._wtables[b, :last_blk] != TRASH_PAGE)
                self._wspan[b] = (int(held[0]), last_blk)
            self._cache, w.tok0, self._carry = _paged_admit_fn(self._paged_cfg)(
                self._cache, w.row_cache, write_ids, np.int32(b), w.first,
                np.uint32(item.seed & 0xFFFFFFFF), np.float32(item.temperature),
                self._carry, np.int32(P), np.asarray(spans, np.int32), *more)
            self._ledger.launched(w.tok0)
            w.first = None
            if not w.snap_blocks:  # the row is in its pages: nobody reads it again (a snapshot's taker does)
                w.row_cache = None
            w.tok0.copy_to_host_async()
            n_own = w.n_shared + len(w.private_pages)
            self._tables[b, :w.n_shared] = w.shared_pages
            self._tables[b, w.n_shared:n_own] = w.private_pages
            self._tables[b, n_own:] = TRASH_PAGE
            self._temps[b] = item.temperature
            with self._wlock:
                self._lengths[b] = P
                self._slots[b] = _Active(item, w.budget, generated=1)
            w.launched = True

    def _stage_admit(self, w: _AdmitWork) -> None:
        """Stage 3, behind the launch of the chunk that carries the rider:
        fetch the first token (the wait for this rider's chain), then host
        bookkeeping — the first token into the reply, the request's timings,
        the prompt's full chunks into the prefix cache so the NEXT request with
        this system prompt shares pages."""
        item = w.item
        b = w.slot
        cpu0 = self._ledger.before_fetch()
        with tel.span("serving.paged.first_token_wait", request_id=item.request_id) as wait:
            tok0 = int(np.asarray(w.tok0))  # fedlint: disable=host-sync one sync per admission, not per decode step, behind the launch of the chunk that carries the rider
            if w.routing is not None:  # the prefill ran before the admit program: already here
                self._note_routing(np.asarray(w.routing), getattr(w.prefill_span, "attrs", None),
                                   ("local_picks", "experts_hit", "row_tiles"))
                w.routing = w.prefill_span = None
        self._ledger.after_fetch(wait, cpu0)
        with tel.span("serving.paged.admit", request_id=item.request_id):
            now_ns = time.perf_counter_ns()
            s = self._slots[b]
            s.tokens.append(tok0)
            s.t_first_ns = now_ns
            ttft = self._note_first_token(item, now_ns, shared=w.n_shared * self._ps)
            self._observe_tenant_ttft(item.tenant, ttft)
            n_prompt_blocks = len(item.prompt) // self._ps  # FULL chunks only
            self._alloc.register_prefix(
                item.prompt, [int(p) for p in self._tables[b, :n_prompt_blocks]],
                None if not self._window else [int(p) for p in self._wtables[b, :n_prompt_blocks]])
            if w.snap_blocks:
                # the trie node this prompt diverged at had pages and no
                # snapshot: it keeps the state this prefill left there
                with tel.span("serving.state.snapshot", request_id=item.request_id,
                              position=w.snap_blocks * self._ps,
                              bytes=self._state_bytes):
                    self._alloc.attach_state(
                        item.prompt, w.snap_blocks, snapshot_of(w.row_cache),
                        self._state_bytes)
            w.row_cache = None
            self._finish_if_done(b, now_ns)
        self._ledger.mark(LAND)

    def _note_first_token(self, item: _Pending, now_ns: int, shared: int) -> float:
        """The request's first token is on the host: its queue and admit
        spans (``queue + admit == ttft_s`` by construction: three readings of
        one clock), the handle's timings, the TTFT series. Returns TTFT."""
        handle = item.handle
        handle.queue_wait_s = (item.t_pop_ns - item.t_submit_ns) / 1e9
        handle.ttft_s = ttft = (now_ns - item.t_submit_ns) / 1e9
        tel.record_span("serving.request.queue", item.t_submit_ns, item.t_pop_ns,
                        request_id=item.request_id, queue_depth=item.queue_depth)
        tel.record_span("serving.request.admit", item.t_pop_ns, now_ns,
                        request_id=item.request_id, prompt_len=len(item.prompt),
                        shared=shared)
        tel.histogram("serving.cb.ttft_seconds").observe(ttft)
        tel.counter("serving.cb.admissions").add(1)
        return ttft

    def _device_copy(self, name: str, host: np.ndarray):
        """The device's copy of a per-slot array the host owns: uploaded again
        only when the host's differs from what was uploaded last. What is
        uploaded is a copy nobody writes: the CPU backend may alias a NumPy
        buffer it is handed, and the host's own arrays change in place while a
        chunk that was given them is still queued."""
        held = self._uploaded.get(name)
        if held is None or not np.array_equal(held[0], host):
            frozen = host.copy()
            held = self._uploaded[name] = (frozen, jax.device_put(frozen))
        return held[1]

    def _step_chunk(self) -> None:
        """Launch the next chunk over the carry as the device holds it, and
        only then land the chunk that was in flight (``_land_chunk``): every
        call launches exactly one ``jit_paged_step``. A row rides if its
        request still has tokens to be launched (``generated < budget``): a
        budget's end needs no token seen."""
        self._ledger.mark(LAUNCH)
        rows = [s if s is not None and s.generated < s.budget else None for s in self._slots]
        active = np.asarray([s is not None for s in rows], bool)
        n_live = int(active.sum())
        # pages the chunk's first token-step reads: each active row's written
        # prefix plus the token it writes; beside B x n_blocks, the share of
        # a whole-table read that paged attention still makes
        lens = self._lengths[active].astype(np.int64) + 1
        attrs = {"pages": int((-(-lens // self._ps)).sum())}
        if self._stateful:  # live slots whose recurrent state the step updates
            attrs["state_slots"] = n_live
        if self._latent_token_bytes:
            _gauge("serving.kv.latent_bytes_live", float((lens - 1).sum() * self._latent_token_bytes))
        more = ()
        if self._window:
            self._slide_windows(active)
            # key positions the chunk's C token-steps read, a layer of each kind
            seen = lens[:, None] + np.arange(self._C)[None, :]
            attrs.update(kv_tokens_full=int(seen.sum()), kv_tokens_window=int(np.minimum(seen, self._window).sum()))
            more = (self._device_copy("wtables", self._wtables),)
        with tel.span("serving.cb.chunk", slots=n_live, **attrs) as chunk_span:
            with tel.span("serving.cb.chunk.dispatch"):
                cache, tok, lengths, keys, toks, *routing = _paged_step_fn(
                    self._paged_cfg, self._B, self._C)(
                    self._params,
                    self._cache,
                    self._device_copy("tables", self._tables),
                    *self._carry,
                    self._device_copy("temps", self._temps),
                    self._device_copy("active", active),
                    *more,
                )
                self._ledger.launched(toks)
                self._cache, self._carry = cache, (tok, lengths, keys)
                for out in (toks, *routing):
                    out.copy_to_host_async()
                before, self._inflight = self._inflight, _Chunk(
                    rows, toks, routing[0] if routing else None,
                    getattr(chunk_span, "attrs", None), time.perf_counter_ns())
                with self._wlock:  # stats() reads the lengths
                    self._lengths[active] += self._C
                for s in rows:
                    if s is not None:
                        s.generated += self._C
            if before is not None:
                self._land_chunk(before)

    def _slide_windows(self, active: np.ndarray) -> None:
        """Before a chunk is launched: every riding row's window table maps
        the blocks the chunk's C token-steps write and read, ``[(L - window +
        1) // page, (L + C - 1) // page]`` for a row of length L, and nothing
        before them. The pages of the blocks wholly behind the chunk's first
        horizon go back to the window group now (a shared one loses this
        request's reference): nothing launched so far reads them later than
        this chunk's launch, and whoever gets them writes them in a program
        launched after it. The group holds a bound for every live request, so
        the pages for the blocks ahead are there."""
        ps, tables = self._ps, self._wtables
        for b in np.flatnonzero(active):
            L = int(self._lengths[b])
            lo, hi = int(self._wspan[b, 0]), int(self._wspan[b, 1])
            new_lo = max(0, L - self._window + 1) // ps
            new_hi = min((L + self._C - 1) // ps + 1, self._n_blocks)
            if new_lo > lo:
                self._alloc.free_window([int(p) for p in tables[b, lo:new_lo]], released=True)
                tables[b, lo:new_lo] = TRASH_PAGE
                lo = new_lo
            if new_hi > hi:
                pages = self._alloc.alloc_window(new_hi - hi)
                if pages is None:
                    raise RuntimeError(f"the window page group has no {new_hi - hi} pages for a live request: "
                                       "it holds fewer than a bound a live request")
                tables[b, hi:new_hi] = pages
                hi = new_hi
            self._wspan[b] = (lo, hi)
        for group, (live, free) in self._alloc.group_pages().items():
            _gauge("serving.kv.pages_live." + group, float(live))
            _gauge("serving.kv.pages_free." + group, float(free))

    def _land_chunk(self, chunk: _Chunk) -> None:
        """Fetch a launched chunk's tokens (``.sync``: the wait for it; near a
        chunk's device time the host has slack, near zero the host is what the
        chip waits for) and do its bookkeeping (``.post``): tokens to the
        requests that still hold the rows they held at its launch, finishes,
        routing counters onto the span that launched it."""
        cpu0 = self._ledger.before_fetch()
        with tel.span("serving.cb.chunk.sync") as sync:
            toks = np.asarray(chunk.toks)  # [B, C]; returns when the chunk is done
        self._ledger.after_fetch(sync, cpu0)
        with tel.span("serving.cb.chunk.post"):
            now_ns = time.perf_counter_ns()
            n_live = sum(1 for s in chunk.rows if s is not None)
            # completion to completion while chunks run back to back; from its
            # own launch for a chunk launched with nothing in flight
            devperf.observe_step("paged_step",
                                 (now_ns - max(chunk.t_launch_ns, self._t_landed_ns)) / 1e9,
                                 tokens=n_live * self._C)
            self._t_landed_ns = now_ns
            tel.counter("serving.cb.tokens_generated").add(n_live * self._C)
            if chunk.routing is not None:
                load = self._note_routing(np.asarray(chunk.routing), chunk.span_attrs, ROUTING_HEAD)
                with self._wlock:
                    self._moe_recent.append(load)
                    recent = np.sum(self._moe_recent, axis=0)
                if recent.sum() > 0:
                    _gauge("serving.moe.load_imbalance", float(recent.max() / recent.mean()))
            for b, s in enumerate(chunk.rows):
                if s is None or self._slots[b] is not s:
                    continue  # masked out, or its request ended or failed a chunk ago: nobody's tokens
                eos = s.pending.eos_ids
                for t in toks[b].tolist():
                    s.tokens.append(t)
                    if (eos is not None and t in eos) or len(s.tokens) >= s.budget:
                        break
                self._finish_if_done(b, now_ns)
        self._ledger.mark(LAND)

    def _note_routing(self, packed: np.ndarray, span_attrs: Optional[dict], names: Tuple[str, ...]) -> np.ndarray:
        """One pass's packed routing (``models/moe.routing_stats``) into the
        ``serving.moe.*`` counters, the engine's totals and, under ``names``,
        the attributes of the span that covered the pass. Returns the pairs by
        held expert."""
        n = len(ROUTING_HEAD)
        head = dict(zip(ROUTING_HEAD, (int(x) for x in packed[:n])))
        tel.counter("serving.moe.tokens_routed").add(head["tokens_routed"])
        tel.counter("serving.moe.local_picks").add(head["local_picks"])
        tel.counter("serving.moe.experts_hit").add(head["experts_hit"])
        tel.counter("serving.moe.row_tiles").add(head["row_tiles"])
        if span_attrs is not None:
            span_attrs.update({k: head[k] for k in names})
        with self._wlock:
            self._moe_totals += packed[:n]
            self._moe_load += packed[n:]
        return packed[n:].astype(np.int64)

    def _finish_if_done(self, b: int, now_ns: int) -> bool:
        """Free slot ``b`` and its pages if its request hit EOS or its token
        budget."""
        with self._wlock:
            s = self._slots[b]
        if s is None:
            return False
        eos = s.pending.eos_ids
        hit_eos = eos is not None and any(t in eos for t in s.tokens)
        if not hit_eos and len(s.tokens) < s.budget:
            return False
        if hit_eos:
            cut = next(i for i, t in enumerate(s.tokens) if t in eos)
            s.tokens = s.tokens[: cut + 1]
        else:
            s.tokens = s.tokens[: s.budget]
        if len(s.tokens) > 1:
            tpot = (now_ns - s.t_first_ns) / 1e9 / (len(s.tokens) - 1)
            s.pending.handle.tpot_s = tpot
            tel.histogram("serving.cb.tpot_seconds").observe(tpot)
        # EOS/budget mid-chunk waste, measured instead of silent: the slot
        # kept burning decode FLOPs until the chunk boundary
        wasted = s.generated - len(s.tokens)
        if wasted > 0:
            tel.counter("serving.wasted_tokens").add(wasted)
        tel.record_span("serving.request.decode", s.t_first_ns, now_ns,
                        request_id=s.pending.request_id, tokens=len(s.tokens),
                        wasted=wasted)
        self._release_slot(b)
        with self._wlock:
            self._slots[b] = None
            self._requests_done += 1
            self._tokens_out += len(s.tokens)
        s.pending.handle._finish(s.tokens)
        return True

    def _release_slot(self, b: int) -> None:
        """Chunk-boundary reclamation: drop the request's reference on
        every page its table maps and point the row at the trash page so
        the slot's remaining mid-chunk scatters can't touch reused pages."""
        pages = [int(p) for p in self._tables[b] if p != TRASH_PAGE]
        self._tables[b, :] = TRASH_PAGE
        if pages:
            self._alloc.free(pages)
        if self._window:
            lo, hi = (int(x) for x in self._wspan[b])
            self._alloc.free_window([int(p) for p in self._wtables[b, lo:hi]])
            self._wtables[b, :] = TRASH_PAGE
            self._wspan[b] = (0, 0)

    def _observe_tenant_ttft(self, tenant: str, ttft: float) -> None:
        dq = self._tenant_ttft.get(tenant)
        if dq is None:
            dq = self._tenant_ttft.setdefault(
                tenant, collections.deque(maxlen=1024))
        dq.append(ttft)
        store = tsdb.active()
        if store is not None:
            # per-tenant TTFT history: the tenant-isolation drill pins a
            # victim tenant's SLO to this series
            store.record_observation(
                "serving.tenant.ttft_seconds." + tenant, ttft)

