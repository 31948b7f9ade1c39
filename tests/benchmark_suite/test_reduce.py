"""The trace reducer on a small hand-made event list: overlapping ops, an op
that encloses others, a gap under a named host span, two device planes."""

import os

import pytest

import fixture_root

fixture_root.bench_imports()

import harness  # noqa: E402

red = harness.load_module(os.path.join(fixture_root.BENCH, "trace", "reduce.py"))
E = red.Event
MS = 1e6


def small_trace():
    ops = [
        E("fusion.1", 0 * MS, 10 * MS),
        E("fusion.2", 5 * MS, 10 * MS),            # overlaps fusion.1: union 0..15
        E("while.3", 40 * MS, 30 * MS),            # encloses two kernel calls
        E("flash_fwd", 42 * MS, 8 * MS),
        E("flash_fwd", 55 * MS, 10 * MS),
        E("fusion.1", 90 * MS, 10 * MS),
    ]
    spans = [E("step_chunk", 10 * MS, 35 * MS), E("admit_wave", 20 * MS, 5 * MS),
             E("client_wait", 60 * MS, 35 * MS)]
    return red.Trace({"/device:TPU:0": ops}, {"/device:TPU:0": [E("jit_run(1)", 0, 15 * MS),
                                                                 E("jit_run(2)", 40 * MS, 30 * MS)]}, spans)


def test_busy_is_the_union_of_intervals():
    r = red.reduce(small_trace(), window=(0.0, 100 * MS))
    assert r["busy_s"] == pytest.approx((15 + 30 + 10) / 1e3)
    assert r["window_s"] == pytest.approx(0.1)
    assert r["idle_pct"] == pytest.approx(45.0)


def test_self_time_leaves_an_enclosing_op_only_what_its_children_leave():
    r = red.reduce(small_trace(), window=(0.0, 100 * MS))
    assert r["op_seconds"]["while.3"] == pytest.approx(0.012)
    assert r["op_seconds"]["flash_fwd"] == pytest.approx(0.018)
    assert r["op_seconds"]["fusion.1"] == pytest.approx(0.020)
    assert r["device_ops"][0][0] == "fusion.1" and len(r["device_ops"]) == 4


def test_gaps_are_named_by_the_innermost_span_open_at_their_middle():
    r = red.reduce(small_trace(), window=(0.0, 100 * MS))
    assert r["idle_gaps"][0] == ["step_chunk", pytest.approx(0.025)]      # 15..40, middle 27.5
    assert r["idle_gaps"][1] == ["client_wait", pytest.approx(0.020)]     # 70..90
    assert r["idle_by_span_s"] == {"step_chunk": pytest.approx(0.025), "client_wait": pytest.approx(0.020)}
    assert red.span_at(small_trace().host_spans, 22 * MS) == "admit_wave"
    assert red.span_at(small_trace().host_spans, 99 * MS) == "no_span"


def test_window_clips_and_defaults_to_the_device_extent():
    r = red.reduce(small_trace(), window=(45 * MS, 95 * MS))
    assert r["busy_s"] == pytest.approx((25 + 5) / 1e3)
    full = red.reduce(small_trace())
    assert full["window_s"] == pytest.approx(0.1)


def test_busy_is_averaged_over_the_device_planes():
    t = small_trace()
    two = red.Trace(dict(t.device_ops, **{"/device:TPU:1": [E("fusion.1", 0, 100 * MS)]}),
                    t.device_modules, t.host_spans)
    r = red.reduce(two, window=(0.0, 100 * MS))
    assert r["n_devices"] == 2
    assert r["busy_s"] == pytest.approx((0.055 + 0.1) / 2)


def test_module_seconds_counts_programs_inside_the_window():
    m = red.module_seconds(small_trace(), 0.0, 100 * MS)
    assert m == {"jit_run(1)": (1, pytest.approx(0.015)), "jit_run(2)": (1, pytest.approx(0.030))}
    assert red.module_seconds(small_trace(), 0.0, 50 * MS) == {"jit_run(1)": (1, pytest.approx(0.015))}


def test_a_trace_without_device_ops_is_refused():
    with pytest.raises(ValueError):
        red.reduce(red.Trace({}, {}, []))
    with pytest.raises(ValueError):
        red.reduce(small_trace(), window=(5.0, 5.0))


def test_decode_module_is_the_one_that_ran_once_per_chunk():
    dec = harness.load_module(os.path.join(fixture_root.BENCH, "metrics", "decode_hbm_roofline.py"))
    mods = {"jit_run(1)": (25, 4.0), "jit_run(2)": (140, 1.5), "jit_run(3)": (24, 0.01)}
    assert dec.decode_module(mods, 25)[0] == "jit_run(1)"
    assert dec.decode_module(mods, 60) is None


# instruction texts as the profiler names device ops on a v5e (my chip run, PR 26), cut short
FWD = ('%attn.18 = (bf16[128,2048,128]{2,1,0:T(8,128)(2,1)S(1)}, f32[128,2048,1]{2,1,0:T(8,128)}) '
       'custom-call(bf16[128,2048,128]{2,1,0} %bitcast.1), custom_call_target="tpu_custom_call"')
DKV = ('%attn.22 = (f32[32,2048,128]{2,1,0:T(8,128)}, f32[32,2048,128]{2,1,0:T(8,128)S(1)}) '
       'custom-call(bf16[128,2048,128]{2,1,0} %bitcast.1903), custom_call_target="tpu_custom_call"')
DQ = ('%attn.40 = f32[128,2048,128]{2,1,0:T(8,128)} custom-call(bf16[128,2048,128]{2,1,0} %b), '
      'custom_call_target="tpu_custom_call"')
UP = ('%fusion.145 = (f32[4,2048]{1,0}, bf16[4,2048,4096]{2,1,0}) fusion(bf16[4,2048,4096]{2,1,0} %copy-done.35, '
      'f32[4096,14336]{1,0:T(8,128)} %params__layer_1____mlp____up_proj____kernel__.1, bf16[4,2048,14336] %fusion.168), '
      'kind=kOutput, calls=%fused_computation.210')


@pytest.mark.parametrize("text,label,kind", [
    (FWD, "attn[mosaic:fwd]", "fwd"), (DKV, "attn[mosaic:dkv]", "dkv"), (DQ, "attn[mosaic:dq]", "dq"),
    (UP, "fusion[layer_*.mlp.up_proj]", None), ("%copy.3 = f32[8]{0} copy(f32[8]{0} %x)", "copy.3", None),
    ("while.3", "while.3", None)])
def test_device_op_labels_and_kernel_kinds(text, label, kind):
    fl = harness.load_module(os.path.join(fixture_root.BENCH, "metrics", "flash_attn_roofline.py"))
    assert red.short_label(text) == label
    assert fl.kind_of(red.short_label(text)) == kind
