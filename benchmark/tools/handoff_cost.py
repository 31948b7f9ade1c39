#!/usr/bin/env python3
"""The page handoff alone, on the chip: ``jit_paged_admit`` (a finished prefill
row into the pool) and ``jit_paged_gather`` (the shared prefix's pages back into
a row) at a serving cell's own shapes, with nothing else on the device. No
weights are made: the pool is the cell's (zeros), the row has a prefill's
shapes and random values, the block tables are what ``_stage_transfer`` /
``_stage_prefill`` would build for a prompt of each length behind the mix's
shared system prompt. Every time is the program's DEVICE time from a profiler
trace (the ``XLA Modules`` line), with the device ops under it by name.

    chiprun -- python3 benchmark/tools/handoff_cost.py [--repo DIR] [--workloads a,b] [--turns 512,2048,...]
    python3 benchmark/tools/handoff_cost.py --ops-of <trace dir or .xplane.pb>

``--repo DIR`` takes ``fedml_tpu`` from another checkout (the parent commit
unpacked beside this one): the benchmark's own files stay this checkout's. The
tool speaks both forms of the admit program (with and without the runtime block
ranges ``spans``). ``--ops-of`` lists the ops under both programs in a trace a
cell's traced run kept (``BENCH_KEEP_TRACE=1``). After every admit the pages the
request owns are compared with the row's blocks, bit for bit, and every other
page but the trash page with what it held. One JSON line a (cell, length) goes
to ``chiprun_out/handoff_cost.jsonl``; the table is printed at the end.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import inspect  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

SERVING = ("internlm2_7b_chat_open", "jamba2_3b_chat_open", "pangu_ultra_moe_chat_open", "trinity_mini_longmix_over")
TURNS = (512, 2048, 8192, 16384)  # behind the mix's system prompt; those a cell's row cannot hold are left out
PROGRAMS = ("jit_paged_admit", "jit_paged_gather")
CALLS = 4


def ops_under(reduce, trace, lo=None, hi=None, top=8):
    """program -> (calls, device seconds a call, [(op, seconds a call), ...]) for the executions of
    ``PROGRAMS`` inside [lo, hi): the ops whose start lies in an execution's interval, by self time."""
    out = {}
    for plane in sorted(trace.device_modules)[:1]:
        ops = sorted(trace.device_ops.get(plane, ()), key=lambda e: e.start_ns)
        for prog in PROGRAMS:
            runs = [m for m in trace.device_modules[plane] if m.name.split("(")[0] == prog
                    and (lo is None or m.start_ns >= lo) and (hi is None or m.end_ns <= hi)]
            if not runs:
                continue
            by_op = collections.defaultdict(float)
            for m in runs:
                inside = [e for e in ops if m.start_ns <= e.start_ns < m.end_ns]
                for name, ns in reduce.self_times(inside, m.start_ns, m.end_ns).items():
                    by_op[name] += ns
            n = len(runs)
            out[prog] = (n, sum(m.dur_ns for m in runs) / n / 1e9,
                         [(k, v / n / 1e9) for k, v in sorted(by_op.items(), key=lambda kv: -kv[1])[:top]])
    return out


def print_ops(found, head=""):
    for prog, (n, secs, ops) in found.items():
        print(f"{head}{prog}: {n} calls, {secs * 1e3:.3f} ms a call", flush=True)
        for name, s in ops:
            print(f"{head}    {s * 1e3:9.4f} ms  {name[:100]}", flush=True)


def tables(rng, n_blocks, ps, prompt, n_shared, window, pages, window_pages):
    """The block tables of one admission, as ``_collect_wave`` / ``_stage_transfer`` make them: the
    prompt's blocks behind ``n_shared`` shared ones in the full group, the blocks of its last ``window``
    tokens in the window group; ``spans`` = a (first, count) a group."""
    import numpy as np

    last = -(-prompt // ps)
    ids = rng.permutation(np.arange(1, pages))[:last].astype(np.int32)
    t = {"shared": np.zeros((n_blocks,), np.int32), "write": np.zeros((n_blocks,), np.int32)}
    t["shared"][:n_shared] = ids[:n_shared]
    t["write"][n_shared:last] = ids[n_shared:last]
    spans = [(n_shared, last - n_shared)]
    if window:
        wids = rng.permutation(np.arange(1, window_pages)).astype(np.int32)
        first_w = max(max(0, prompt - window + 1) // ps, n_shared)
        tail = max(0, n_shared * ps - window + 1) // ps          # PagedKVAllocator._window_tail
        t["wshared"], t["wwrite"] = np.zeros((n_blocks,), np.int32), np.zeros((n_blocks,), np.int32)
        t["wshared"][tail:n_shared] = wids[:n_shared - tail]
        t["wwrite"][first_w:last] = wids[n_shared - tail:n_shared - tail + last - first_w]
        spans.append((first_w, last - first_w))
    t["spans"] = np.asarray(spans, np.int32)
    return t


def page_leaves(pcfg, pool):
    """(path, is a window layer's) of the pool's K/V (or latent) leaves. By hand: the parent commit's
    ``paged_kv``, which this tool also drives, has no ``_page_groups``."""
    import jax

    from fedml_tpu.models.mamba import STATE_LEAVES
    from fedml_tpu.serving import paged_kv
    from fedml_tpu.train.llm.generation import _leaf_name

    windowed = paged_kv._window_layer_names(pcfg)
    return [(path, paged_kv._in_window_layer(path, windowed))
            for path, x in jax.tree_util.tree_flatten_with_path(pool)[0] if x.ndim and _leaf_name(path) not in STATE_LEAVES]


def check_pages(leaves, before, after, row, t, ps):
    """The owned pages hold the row's blocks bit for bit; every other page but the trash page is as it was."""
    import jax
    import numpy as np

    from fedml_tpu.serving.paged_kv import TRASH_PAGE
    from fedml_tpu.train.llm.generation import _leaf_at

    bad = []
    for path, win in leaves:
        first, count = (int(v) for v in t["spans"][int(win)])
        ids = t["wwrite" if win else "write"][first:first + count]
        src = np.asarray(_leaf_at(row, path))[0]
        new, old = np.asarray(_leaf_at(after, path)), _leaf_at(before, path)
        want = src[first * ps:(first + count) * ps].reshape((count, ps) + src.shape[1:])
        if not np.array_equal(new[ids], want):
            bad.append(("owned", jax.tree_util.keystr(path)))
        rest = np.setdiff1d(np.arange(new.shape[0]), np.append(ids, TRASH_PAGE))
        if not np.array_equal(new[rest], old[rest]):
            bad.append(("others", jax.tree_util.keystr(path)))
    return bad


def one_cell(args, root, workload, allow_cpu, out):
    import jax
    import jax.numpy as jnp
    import numpy as np

    import harness

    from fedml_tpu.models.mamba import unpack_state
    from fedml_tpu.models.transformer import TransformerLM
    from fedml_tpu.serving import paged_kv
    from fedml_tpu.train.llm.generation import _prefill_fn

    cell = harness.Cell(root, workload)
    ctx = harness.Ctx(cell, args.seed, 1.0, True, T0, allow_cpu=allow_cpu)
    drv = cell.driver()
    cfg = drv.model_config(ctx)
    p, tr = ctx.workload["program"], ctx.traffic
    ps, B = int(p["page_size"]), int(p["num_slots"])
    base = paged_kv.row_config(cfg)
    window = base.sliding_window if getattr(base, "window_layers", ()) else 0
    n_blocks = base.max_seq_len // ps
    pages = int(p.get("num_pages") or B * n_blocks + 1)
    wpages = (B + 1) * paged_kv.window_bound(window, int(p["decode_chunk"]), ps) + 1 if window else 0
    pcfg = paged_kv.paged_config(base, page_size=ps, num_pages=pages, **({"window_pages": wpages} if window else {}))
    shapes = jax.eval_shape(lambda k: TransformerLM(base).init(k, jnp.zeros((1, 8), jnp.int32))["params"],
                            jax.random.PRNGKey(0))
    stateful = bool(getattr(base, "has_recurrent_state", False))

    rng = np.random.default_rng([args.seed & 0xFFFFFFFF, 38])
    key = jax.random.PRNGKey(args.seed & 0x7FFFFFFF)

    def filled(tree, salt):
        """Random values in a tree of shapes (scalars, the write indices, stay 0)."""
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        made = [jnp.zeros(s.shape, s.dtype) if s.ndim == 0 or not jnp.issubdtype(s.dtype, jnp.floating)
                else jax.random.normal(jax.random.fold_in(key, salt + i), s.shape, jnp.float32).astype(s.dtype)
                for i, s in enumerate(leaves)]
        return jax.tree_util.tree_unflatten(treedef, made)

    snap = np.int32(0) if stateful else None
    row_shapes, first_shape = jax.eval_shape(
        _prefill_fn(base, 1, 16), shapes, jnp.zeros((1, 16), jnp.int32), np.int32(16), snap)[:2]
    row = filled(row_shapes, 1000)
    first = filled(first_shape, 5000)
    pool = filled(jax.eval_shape(lambda: paged_kv.paged_pool_init(shapes, pcfg, B)), 9000) if args.check \
        else paged_kv.paged_pool_init(shapes, pcfg, B)
    leaves = page_leaves(pcfg, pool)
    state = paged_kv.snapshot_of(row) if stateful else None
    carry = (jnp.zeros((B,), jnp.int32), jnp.zeros((B,), jnp.int32), jnp.zeros((B, 2), jnp.uint32))
    admit, gather = paged_kv._paged_admit_fn(pcfg), paged_kv._paged_gather_fn(pcfg)
    ranged = "spans" in inspect.signature(admit).parameters
    sys_len = int(tr.get("system_prompt_tokens", 0))
    n_shared = sys_len // ps
    own = sorted({int(v) for v in tr["user_tokens"]["values"]})
    turns = sorted({n for n in (own[0], own[-1], *args.turns) if sys_len + n + 1 <= base.max_seq_len})

    def admit_args(pool, t, prompt):
        head = (pool, row, t["write"], np.int32(3 % B), first, np.uint32(7), np.float32(0.0), carry, np.int32(prompt))
        tail = (t["wwrite"],) if window else ()
        return head + ((t["spans"],) if ranged else ()) + tail

    def gather_args(pool, t):
        return (pool, t["shared"], np.int32(n_shared * ps), state) + ((t["wshared"],) if window else ())

    ctx.log(f"{workload}: {n_blocks} blocks of {ps}, {pages} pages" + (f" + {wpages} window pages" if window else "")
            + f", admit takes block ranges: {ranged}; turns {turns} behind {sys_len}")
    per_turn = {}
    for turn in turns:   # compile, check, and what the compiler says of the pool
        prompt = sys_len + turn
        t = per_turn[turn] = tables(rng, n_blocks, ps, prompt, n_shared, window, pages, wpages)
        if turn == turns[0]:
            mem = admit.lower(*admit_args(pool, t, prompt)).compile().memory_analysis()
            pool_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(pool))
            memory = {k: int(getattr(mem, k + "_size_in_bytes", -1)) for k in ("temp", "alias", "argument", "output")}
            memory["pool_bytes"] = pool_bytes
            ctx.log(f"admit program memory: {memory}")
        before = jax.tree_util.tree_map(np.asarray, pool) if args.check else None
        pool, tok0, _ = admit(*admit_args(pool, t, prompt))
        jax.block_until_ready(gather(*gather_args(pool, t)))
        if args.check:
            bad = check_pages(leaves, before, pool, unpack_state(pcfg, row), t, ps)
            if bad:
                raise harness.HarnessError(f"{workload} turn {turn}: pages differ: {bad[:6]}")
    tracer = ctx.tracer
    tracer.start()
    for turn in turns:
        t, prompt = per_turn[turn], sys_len + turn
        with harness.span(f"handoff:{turn}"):
            for _ in range(CALLS):
                pool, tok0, _ = admit(*admit_args(pool, t, prompt))
            jax.block_until_ready(tok0)
            jax.block_until_ready([gather(*gather_args(pool, t)) for _ in range(CALLS)])
        time.sleep(0.02)   # the next length's executions well clear of this span's end
    trace = tracer.stop()
    spans = {s.name: s for s in trace.host_spans}
    for turn in turns:
        s = spans[f"handoff:{turn}"]
        found = ops_under(tracer.reduce, trace, s.start_ns, s.end_ns + 1e6)   # every execution ended inside the span
        t = per_turn[turn]
        owned = sum(int(t["spans"][int(win)][1]) for _, win in leaves)
        row_out = {"workload": workload, "repo": args.repo or ".", "ranged": ranged, "turn": turn,
                   "prompt": sys_len + turn, "blocks_row": n_blocks * len(leaves), "blocks_owned": owned,
                   "device": ctx.device, "memory": memory,
                   **{prog + "_ms": found[prog][1] * 1e3 for prog in found},
                   **{prog + "_ops": [[k, v * 1e3] for k, v in found[prog][2]] for prog in found}}
        out.append(row_out)
        print(f"== {workload} turn {turn} (prompt {sys_len + turn}): owns {owned} of {row_out['blocks_row']} page writes")
        print_ops(found, "   ")
    return out


def main(argv=None, root: str = ROOT, allow_cpu: bool = False) -> int:
    """``root`` / ``allow_cpu`` are for the tests' rehearsal at a tiny size."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--repo", default="")
    ap.add_argument("--workloads", default=",".join(SERVING))
    ap.add_argument("--turns", default=",".join(str(t) for t in TURNS))
    ap.add_argument("--seed", type=int, default=2**31 + 3801)
    ap.add_argument("--check", type=int, default=1)
    ap.add_argument("--ops-of", default="")
    args = ap.parse_args(argv)
    args.turns = [int(t) for t in args.turns.split(",") if t]
    for p in (BENCH, os.path.abspath(args.repo) if args.repo else ROOT):
        while p in sys.path:
            sys.path.remove(p)
        sys.path.insert(0, p)
    import harness

    if args.ops_of:
        reduce = harness.load_module(os.path.join(BENCH, "trace", "reduce.py"))
        path = args.ops_of if args.ops_of.endswith(".pb") else reduce.find_xplane(args.ops_of)
        print_ops(ops_under(reduce, reduce.load_xplane(path), top=16))
        return 0
    harness.place_compile_cache(root)
    rows = []
    for workload in args.workloads.split(","):
        one_cell(args, root, workload, allow_cpu, rows)
    os.makedirs(os.path.join(root, "chiprun_out"), exist_ok=True)
    with open(os.path.join(root, "chiprun_out", "handoff_cost.jsonl"), "a") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    print("| cell | fedml_tpu from | prompt | page writes owned / of the row | admit ms | gather ms |")
    print("|---|---|---|---|---|---|")
    for r in rows:
        print(f"| {r['workload']} | {r['repo']} | {r['prompt']} | {r['blocks_owned']} / {r['blocks_row']} | "
              f"{r.get('jit_paged_admit_ms', float('nan')):.3f} | {r.get('jit_paged_gather_ms', float('nan')):.3f} |")
    return 0


if __name__ == "__main__":
    sys.exit(main())
