"""The general traffic generators: one per ``kind`` of traffic file.

A traffic mix is a data file under ``benchmark/traffic/``; a later PR adds a
mix by adding a file of parameters for a kind that exists. Everything is
drawn from ``--seed``; the program receives only the generated inputs.

kinds
  packed_documents   training batches: documents of drawn lengths packed
                     into fixed sequences without splitting, the rest of a
                     sequence padded (mask 0)
  open_loop_chat     serving requests on a schedule: every seed gets the
                     SAME multiset of (system?, user length, reply budget)
                     and of inter-arrival gaps, in another order, so runs
                     differ in order and content, never in amount of work;
                     ``close_with_longest`` makes the request due last one of
                     the longest replies, so the drain has one length
"""

from __future__ import annotations

import math
from typing import Dict, List, Tuple

import numpy as np


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, *[int(s) for s in stream]])


# -- training ------------------------------------------------------------------

def packed_batch(tr: dict, seed: int, index: int, batch: int, vocab: int) -> Tuple[np.ndarray, np.ndarray]:
    """Batch ``index`` of the stream for ``seed``: (tokens, mask) [batch, seq_len].
    Token ids are uniform over the vocabulary (0 is the pad id and is not
    drawn); every row differs."""
    if tr["kind"] != "packed_documents":
        raise ValueError(f"traffic kind {tr['kind']!r} does not make training batches")
    T = int(tr["seq_len"])
    rng = _rng(seed, 1, index)
    toks = rng.integers(1, vocab, (batch, T), dtype=np.int32)
    mask = np.ones((batch, T), np.float32)
    d = tr["doc_len"]
    for r in range(batch):
        used = 0
        while True:
            n = int(np.clip(rng.lognormal(math.log(d["median"]), d["sigma"]), d["min"], d["max"]))
            if used + n > T:
                break
            used += n
        if used == 0:
            used = T  # one document longer than the sequence: truncated, no padding
        toks[r, used:] = 0
        mask[r, used:] = 0.0
    return toks, mask


# -- serving -------------------------------------------------------------------

def _stratified(values: List, weights: List[float], n: int) -> List:
    """n items with each value as near its weight as whole numbers allow."""
    w = np.asarray(weights, float) / sum(weights)
    counts = np.floor(w * n).astype(int)
    for i in np.argsort(-(w * n - counts))[: n - counts.sum()]:
        counts[i] += 1
    out: List = []
    for v, c in zip(values, counts):
        out += [v] * int(c)
    return out


def open_loop_requests(tr: dict, seed: int, seconds: float, vocab: int) -> Dict:
    """The schedule of one run: ``n = round(rate x seconds)`` requests.
    Gaps are the n mid-quantiles of Exp(rate) (a Poisson process's gaps),
    shuffled; for ``arrivals: bursty`` the shuffled gaps are grouped so that
    ``burst_size`` requests arrive ``burst_gap_s`` apart and the saved time
    goes before the burst. Returns due times and token lists."""
    if tr["kind"] != "open_loop_chat":
        raise ValueError(f"traffic kind {tr['kind']!r} does not make requests")
    rate = float(tr["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    rng = _rng(seed, 2)
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u) / rate
    gaps *= (n / rate) / gaps.sum() * (n / (n + 1.0))  # last arrival just inside the window
    rng.shuffle(gaps)
    if tr.get("arrivals", "poisson") == "bursty":
        k, g = int(tr["burst_size"]), float(tr["burst_gap_s"])
        for i in range(0, n, k):
            chunk = gaps[i:i + k]
            saved = float(np.maximum(chunk[1:] - g, 0).sum())
            chunk[1:] = np.minimum(chunk[1:], g)
            chunk[0] += saved
    due = np.cumsum(gaps)
    sys_len = int(tr.get("system_prompt_tokens", 0))
    with_sys = _stratified([True, False], [tr.get("system_prompt_share", 0.0),
                                           1.0 - tr.get("system_prompt_share", 0.0)], n)
    user = _stratified(tr["user_tokens"]["values"], tr["user_tokens"]["weights"], n)
    new = _stratified(tr["max_new_tokens"]["values"], tr["max_new_tokens"]["weights"], n)
    pairing = _rng(0, 99)               # the same (system?, turn, budget) triples for every seed ...
    for lst in (with_sys, user, new):
        pairing.shuffle(lst)
    order = rng.permutation(n)          # ... in an order of the seed's own
    with_sys, user, new = ([lst[j] for j in order] for lst in (with_sys, user, new))
    if tr.get("close_with_longest"):
        # the request due last is one of the longest replies, whatever the order: the drain
        # after the window then has one length, and tokens/s does not swing with the order
        j = max(range(n), key=lambda i: (new[i], i))
        for lst in (with_sys, user, new):
            lst[j], lst[n - 1] = lst[n - 1], lst[j]
    system = _rng(seed, 3).integers(1, vocab, sys_len).tolist()
    requests = []
    for i in range(n):
        turn = rng.integers(1, vocab, int(user[i])).tolist()
        requests.append({
            "index": i, "due_s": float(due[i]), "system": bool(with_sys[i]),
            "prompt": (system + turn) if with_sys[i] else turn,
            "max_new_tokens": int(new[i]), "temperature": float(tr.get("temperature", 0.0)),
        })
    return {"requests": requests, "system_prompt": system, "rate_per_s": rate}


def warmup_prompts(tr: dict, seed: int, vocab: int) -> List[List[int]]:
    """One prompt per shape the mix can send, from a stream of its own: the
    system prompt alone first (it fills the prefix cache), every user length
    without it, then every user length after it."""
    rng = _rng(seed, 4)
    sys_len = int(tr.get("system_prompt_tokens", 0))
    system = _rng(seed, 3).integers(1, vocab, sys_len).tolist()
    lens = sorted(set(int(v) for v in tr["user_tokens"]["values"]))
    out = [system] if sys_len else []
    if tr.get("system_prompt_share", 0.0) < 1.0:
        out += [rng.integers(1, vocab, n).tolist() for n in lens]
    if sys_len and tr.get("system_prompt_share", 0.0) > 0.0:
        out += [system + rng.integers(1, vocab, n).tolist() for n in lens]
    return out
