"""The generators: a seed reproduces its schedule, lengths and arrivals land
where the mix says, every seed gets the same amount of work, and text made for
the endpoint encodes to exactly the wanted number of tokens."""

import os

import numpy as np
import pytest

import fixture_root

fixture_root.bench_imports()

import harness  # noqa: E402
import traffic  # noqa: E402

CHAT = harness.load_json(os.path.join(fixture_root.BENCH, "traffic", "chat_open.json"))
PACK = harness.load_json(os.path.join(fixture_root.BENCH, "traffic", "pack2k.json"))
BIG_SEED = 2**31 + 12345


def test_schedule_reproduces_from_a_seed_and_differs_across_seeds():
    a = traffic.open_loop_requests(CHAT, BIG_SEED, 30, 92544)
    b = traffic.open_loop_requests(CHAT, BIG_SEED, 30, 92544)
    c = traffic.open_loop_requests(CHAT, BIG_SEED + 1, 30, 92544)
    assert a == b
    assert [r["due_s"] for r in a["requests"]] != [r["due_s"] for r in c["requests"]]
    assert a["system_prompt"] != c["system_prompt"]


@pytest.mark.parametrize("seed", [0, 7, BIG_SEED])
def test_every_seed_gets_the_same_work_in_another_order(seed):
    base = traffic.open_loop_requests(CHAT, 1, 30, 92544)["requests"]
    got = traffic.open_loop_requests(CHAT, seed, 30, 92544)["requests"]

    def shape(rs):
        return sorted((r["system"], len(r["prompt"]), r["max_new_tokens"]) for r in rs)

    def gaps(rs):
        due = np.array([r["due_s"] for r in rs])
        return np.sort(np.diff(np.concatenate([[0.0], due])))

    assert len(got) == len(base) == round(CHAT["rate_per_s"] * 30)
    assert sorted(len(r["prompt"]) for r in got) == sorted(len(r["prompt"]) for r in base)
    assert sorted(r["max_new_tokens"] for r in got) == sorted(r["max_new_tokens"] for r in base)
    assert sum(r["system"] for r in got) == sum(r["system"] for r in base)
    np.testing.assert_allclose(gaps(got), gaps(base), rtol=1e-9)
    assert shape(got) != [] and all(0 < r["due_s"] < 30 for r in got)


def test_lengths_land_in_their_buckets_and_shares():
    rs = traffic.open_loop_requests(CHAT, 3, 30, 92544)["requests"]
    sys_len = CHAT["system_prompt_tokens"]
    users = [len(r["prompt"]) - (sys_len if r["system"] else 0) for r in rs]
    assert set(users) <= set(CHAT["user_tokens"]["values"])
    assert all(u % 16 == 0 for u in users) and sys_len % 16 == 0   # 16-token prefill buckets
    assert set(r["max_new_tokens"] for r in rs) <= set(CHAT["max_new_tokens"]["values"])
    assert abs(sum(r["system"] for r in rs) / len(rs) - CHAT["system_prompt_share"]) < 0.01
    for v, w in zip(CHAT["user_tokens"]["values"], CHAT["user_tokens"]["weights"]):
        assert abs(users.count(v) / len(rs) - w) <= 1.0 / len(rs) + 1e-9
    assert max(len(r["prompt"]) + r["max_new_tokens"] for r in rs) <= 2048
    assert all(1 <= t < 92544 for r in rs for t in r["prompt"])


@pytest.mark.parametrize("seed", [2, 3, BIG_SEED])
def test_the_request_due_last_is_one_of_the_longest_replies(seed):
    rs = traffic.open_loop_requests(CHAT, seed, 40, 92544)["requests"]
    assert CHAT["close_with_longest"] is True
    assert rs[-1]["max_new_tokens"] == max(CHAT["max_new_tokens"]["values"])
    assert rs[-1]["due_s"] == max(r["due_s"] for r in rs)
    free = traffic.open_loop_requests(dict(CHAT, close_with_longest=False), seed, 40, 92544)["requests"]
    assert sorted(r["max_new_tokens"] for r in free) == sorted(r["max_new_tokens"] for r in rs)


def test_arrivals_are_poisson_shaped_at_the_stated_rate():
    rs = traffic.open_loop_requests(CHAT, 5, 30, 92544)["requests"]
    due = np.array([r["due_s"] for r in rs])
    gaps = np.diff(np.concatenate([[0.0], due]))
    assert len(rs) / 30 == pytest.approx(CHAT["rate_per_s"], rel=0.01)
    assert np.std(gaps) / np.mean(gaps) == pytest.approx(1.0, abs=0.12)   # exponential gaps
    assert np.all(np.diff(due) > 0)


def test_bursty_arrivals_keep_the_mean_rate():
    mix = dict(CHAT, arrivals="bursty", burst_size=8, burst_gap_s=0.005)
    rs = traffic.open_loop_requests(mix, 5, 30, 92544)["requests"]
    due = np.array([r["due_s"] for r in rs])
    gaps = np.diff(np.concatenate([[0.0], due]))
    assert due[-1] == pytest.approx(traffic.open_loop_requests(CHAT, 5, 30, 92544)["requests"][-1]["due_s"])
    assert np.std(gaps) / np.mean(gaps) > 1.5


def test_warmup_covers_every_shape_the_mix_can_send():
    warm = traffic.warmup_prompts(CHAT, 9, 92544)
    plan = traffic.open_loop_requests(CHAT, 9, 30, 92544)
    assert warm[0] == plan["system_prompt"]                       # fills the prefix cache first
    lens = sorted(len(p) for p in warm[1:])
    want = sorted(CHAT["user_tokens"]["values"] + [256 + v for v in CHAT["user_tokens"]["values"]])
    assert lens == want
    assert all(p[:256] == plan["system_prompt"] for p in warm if len(p) > 1024 or len(p) in (320, 384))


def test_text_encodes_to_exactly_its_tokens():
    drv = harness.load_module(os.path.join(fixture_root.BENCH, "drivers", "llm_serve.py"))
    tok = drv.char_tokenizer(92544)
    ids = traffic.open_loop_requests(CHAT, 11, 30, 92544)["requests"][0]["prompt"]
    text = drv.text_of(ids)
    assert tok.encode(text) == ids
    assert tok.decode(ids) == text
    assert "</s>" not in tok.special_tokens


@pytest.mark.parametrize("seed", [1, BIG_SEED])
def test_packed_batches_reproduce_and_are_masked_where_padded(seed):
    a = traffic.packed_batch(PACK, seed, 3, 4, 32768)
    b = traffic.packed_batch(PACK, seed, 3, 4, 32768)
    c = traffic.packed_batch(PACK, seed, 4, 4, 32768)
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1], b[1])
    assert not np.array_equal(a[0], c[0])
    toks, mask = a
    assert toks.shape == mask.shape == (4, 2048) and toks.dtype == np.int32
    assert len({row.tobytes() for row in toks}) == 4               # rows all differ
    assert np.all((toks == 0) == (mask == 0))                      # padding is exactly the masked part
    for row in mask:                                               # documents first, padding last
        assert np.all(np.diff(row) <= 0)
    assert 0.5 < mask.mean() <= 1.0
    assert toks.max() < 32768


def test_wrong_kind_is_refused():
    with pytest.raises(ValueError):
        traffic.packed_batch(CHAT, 1, 0, 4, 100)
    with pytest.raises(ValueError):
        traffic.open_loop_requests(PACK, 1, 10, 100)
