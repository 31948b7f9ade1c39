"""The reader ``ssm_step_roofline`` (``benchmark/metrics``): the one-token step
kernel's summed device time against the live slots' state bytes of the chunks
launched in the traced part, on hand-made spans and a hand-made reduced trace
(no JAX, no chip)."""

import os

import pytest

import fixture_root

fixture_root.bench_imports()

import flops_jamba  # noqa: E402
import harness  # noqa: E402
import program_spans  # noqa: E402

READER = harness.load_module(os.path.join(fixture_root.BENCH, "metrics", "ssm_step_roofline.py"))
JAMBA = harness.load_json(os.path.join(fixture_root.BENCH, "configs", "jamba2-3b.json"))
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
WINDOW = {"t_start": 0.0, "t_close": 40.0, "trace_t0": 10.0, "trace_t1": 15.0, "decode_chunk": 8}
SPANS = [("serving.cb.chunk", 9.0, {"slots": 30, "state_slots": 30}),                 # before the traced part
         ("serving.cb.chunk", 10.2, {"slots": 27, "state_slots": 27, "state_slots_skipped": 37}),
         ("serving.cb.chunk", 10.3, {"slots": 3}),                                     # no state attribute
         ("serving.cb.prefill", 10.4, {"prompt_len": 300, "shared": 256}),
         ("serving.cb.chunk", 14.9, {"slots": 28, "state_slots": 28, "state_slots_skipped": 36}),
         ("serving.cb.chunk", 15.5, {"slots": 20, "state_slots": 20})]                 # after it


def _run(op_seconds, peaks=PEAKS, config=JAMBA, window=WINDOW):
    snap = {"epoch_perf_ns": 0, "spans": [{"name": n, "t0_ns": int(t * 1e9), "dur_ns": 1000, "attrs": a}
                                          for n, t, a in SPANS]}
    ctx = type("Ctx", (), {"peaks": peaks, "config": config})()
    return {program_spans.SNAPSHOT_KEY: snap, "ctx": ctx, "trace": {"op_seconds": op_seconds}, "window": dict(window)}


def test_what_the_kernel_must_move_comes_from_the_chunks_of_the_traced_part():
    assert READER.slot_steps(_run({})) == (27 + 28) * 8
    assert READER.slot_steps(_run({}, window={"t_start": 0.0, "t_close": 40.0, "decode_chunk": 8})) == 0
    assert READER.slot_bytes(JAMBA) == 2 * 16 * 5120 * 4     # one slot's h in one layer, read and written
    assert flops_jamba.n_layers(JAMBA, "mamba") == 26


def test_the_kernel_is_found_by_its_name_and_set_against_the_live_slots_bytes():
    least = (27 + 28) * 8 * 26 * 2 * 16 * 5120 * 4 / 819e9
    run = _run({"ssm_step[mosaic:dq_]": 0.75 * least, "ssm_step[mosaic:dq_].1": 1.25 * least,
                "fusion.2681": 0.16, "selective_scan[mosaic:dq_]": 0.5})
    assert READER.read(run) == pytest.approx(50.0)


@pytest.mark.parametrize("case", ["parent", "no chip", "no mamba layer", "untraced"])
def test_it_says_nothing_where_there_is_nothing_to_read(case):
    """A program without the kernel (the parent: its state update is an XLA fusion), a run without the
    chip's peaks, a model without Mamba layers, a run without a trace: nothing, and nothing raised."""
    run = _run({"fusion.2533": 0.04} if case == "parent" else {"ssm_step[mosaic:dq_]": 0.01},
               peaks=None if case == "no chip" else PEAKS,
               config={"hidden_size": 64} if case == "no mamba layer" else JAMBA)
    if case == "untraced":
        run["trace"] = None
    assert READER.read(run) is None
