"""The trace reduction against a small trace recorded on a TPU v5e
(``bench/testdata/trace.xplane.pb.gz``, from ``bench/tools/record_trace.py``):
a jitted 1024x1024 matrix product and one Pallas dequant of s8[512,1024]
under a ``bench/request`` span, a 50 ms host sleep under ``bench/idle``, then
a jitted two-layer scan around the Pallas flash-attention kernel of
q/k/v bf16[2,2,256,128] under a second ``bench/request``.  The numbers below
were read off the raw events by hand."""
from __future__ import annotations

import os
from types import SimpleNamespace

import pytest

from bench_tiny import ROOT

from bench import harness, trace_reduce

TRACE = os.path.join(ROOT, "bench", "testdata", "trace.xplane.pb.gz")
WHOLE = (0.046, 0.101)      # seconds: every device event of the recording


@pytest.fixture(scope="module")
def prof():
    return trace_reduce.load(TRACE)


def test_busy_time_is_the_union_of_operations(prof):
    r = trace_reduce.reduce(prof, window=WHOLE)
    # matmul program: copy-start 14 ns, copy-done 3109 ns, fusion 12593 ns
    # (three disjoint operations); dequant program: copy 612 ns + kernel
    # 3761 ns; scan program: one while loop of 26034 ns spanning its body
    assert r.busy_s == pytest.approx((14 + 3109 + 12593 + 612 + 3761 + 26034) * 1e-9, rel=1e-9)
    assert r.window_s == pytest.approx(0.055)
    assert len(r.modules("jit_")) == 3
    assert r.modules("jit_layers") == [pytest.approx((0.098206688, 0.098232966))]


def test_pallas_calls_and_their_readers(prof):
    r = trace_reduce.reduce(prof, window=WHOLE)
    calls = r.custom_calls()
    assert len(calls) == 3
    dequant = harness.load_module(f"{harness.BENCH_DIR}/metrics/dequant_roofline.py", "r_dq")
    flash = harness.load_module(f"{harness.BENCH_DIR}/metrics/flash_attention_roofline.py", "r_fa")
    dq = [c for c in calls if dequant.work(c)]
    fa = [c for c in calls if flash.work(c)]
    assert len(dq) == 1 and len(fa) == 2
    assert dq[0].seconds == pytest.approx(3761e-9)
    assert dequant.work(dq[0]) == (512 * 1024, 512 * 1024 * 3 + 512 * 8 * 4)
    assert all(c.seconds == pytest.approx(8828e-9) for c in fa)
    run = harness.RunData(None, 0.0, 0.0, [], {}, harness.peaks_for("TPU v5 lite"), r)
    # 1,589,248 B at 819 GB/s = 1.9405 us against 3.761 us
    assert dequant.read(run) == pytest.approx(100 * 1589248 / 819e9 / 3761e-9, rel=1e-6)


def test_idle_gaps_are_named_by_the_host_span(prof):
    r = trace_reduce.reduce(prof, window=WHOLE)
    gaps = r.breakdown()["idle_gaps"]
    name, seconds = gaps[0]
    assert name == "bench/idle > $time sleep"
    # from the end of the dequant program to the start of the scan's loop
    assert seconds == pytest.approx(0.098206689 - 0.047340805, rel=1e-6)
    ops = dict(r.breakdown()["device_ops"])
    assert ops["jit_layers/pallas bf16,bf16,bf16->bf16"] == pytest.approx(2 * 8828e-9)
    assert "jit_layers/while" not in ops       # loops are not counted twice


def test_default_window_is_the_bench_spans(prof):
    """Without a ``bench/window`` span the window runs from the first to the
    last ``bench/`` span.  The device clock runs about 1.1 ms early here, so
    the first request's device work falls before it."""
    r = trace_reduce.reduce(prof)
    assert r.window == pytest.approx((0.047443422, 0.100319373))
    assert r.busy_s == pytest.approx(26034e-9, rel=1e-6)
    assert len(r.custom_calls()) == 2


def event(name, start_ns, duration_ns):
    return SimpleNamespace(name=name, start_ns=start_ns, duration_ns=duration_ns)


def test_host_spans_are_read_from_the_thread_that_holds_them():
    """The host plane names each line after its thread: ``python`` in the
    recording, ``python3`` under ``python3 bench/run.py``.  The spans are
    read from whichever line holds a ``bench/`` span, and no other."""
    NS = SimpleNamespace
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[event("jit_step(1)", 2_000, 3_000)]),
        NS(name="XLA Ops", events=[event("%fusion = f32[8] fusion()", 2_000, 3_000)]),
    ])
    host = NS(name="/host:CPU", lines=[
        NS(name="python3", events=[event("bench/window", 1_000, 9_000),
                                   event("bench/scan_call", 1_500, 4_000)]),
        NS(name="tf_pjrt/17", events=[event("PjRtExecute", 1_800, 500)]),
    ])
    r = trace_reduce.reduce(NS(planes=[device, host]))
    assert r.window == pytest.approx((1e-6, 10e-6))
    assert [name for name, _, _ in r.host] == ["bench/window", "bench/scan_call"]
    assert r.busy_s == pytest.approx(3e-6)
    assert r.modules("jit_step") == [pytest.approx((2e-6, 5e-6))]
    assert r.named_modules("jit_") == [("jit_step(1)", pytest.approx(2e-6), pytest.approx(5e-6))]


def test_decode_is_the_engine_program_that_runs_most_often():
    """The engine's prefill and decode programs are both ``jit__unknown``,
    told apart by their hash: decode (``b``) runs most often, and prefills
    at two lengths (``a``, ``c``) split its spans.  The step dispatched after
    the last served token runs after its request's host span, and an eager
    ``jit_argmax`` between steps is not a decode step."""
    NS = SimpleNamespace
    us = 1_000
    modules = [("jit__unknown(a)", 9 * us, 20 * us),       # prefill
               ("jit_argmax(x)", 30 * us, 1 * us),
               ("jit__unknown(b)", 32 * us, 4 * us),       # decode
               ("jit__unknown(b)", 40 * us, 4 * us),       # decode
               ("jit__unknown(b)", 46 * us, 4 * us),       # decode, past the span
               ("jit__unknown(c)", 99 * us, 30 * us),      # prefill, other length
               ("jit__unknown(b)", 140 * us, 6 * us)]      # decode
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[event(*m) for m in modules]),
        NS(name="XLA Ops", events=[event("%fusion = f32[8] fusion()", s, d)
                                   for _, s, d in modules]),
    ])
    host = NS(name="/host:CPU", lines=[NS(name="python3", events=[
        event("bench/window", 0, 200 * us),
        event("bench/request", 10 * us, 35 * us),
        event("bench/request", 100 * us, 50 * us),
    ])])
    r = trace_reduce.reduce(NS(planes=[device, host]))
    from bench.engine_programs import decode_program, decode_spans

    assert decode_program(r) == "jit__unknown(b)"
    assert decode_spans(r) == [pytest.approx((32e-6, 50e-6)), pytest.approx((140e-6, 146e-6))]
    run = harness.RunData(None, 0.0, 0.0, [], {}, None, r)
    step = harness.load_module(f"{harness.BENCH_DIR}/metrics/decode_step_ms.py", "r_ds")
    idle = harness.load_module(f"{harness.BENCH_DIR}/metrics/decode_idle_share.py", "r_di")
    assert step.read(run) == pytest.approx(1000 * 18e-6 / 4)
    # decode spans 32-50 us (6 us idle) and 140-146 us (none)
    assert idle.read(run) == pytest.approx(100 * 6 / 24)
    assert r.breakdown()["device_ops"][0][0] == "jit__unknown/fusion"
