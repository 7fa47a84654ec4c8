"""The readers of the program's own spans, on small synthetic traces built
as ``trace_reduce`` reads them (times in microseconds, read off by hand).
A span that does not lie wholly inside ``bench/window`` is not read, and a
trace without the program's spans (a program that records none) reads
None."""
from __future__ import annotations

from types import SimpleNamespace as NS

import pytest

from bench_tiny import ROOT  # noqa: F401  (puts the checkout on sys.path)

from bench import harness, trace_reduce

US = 1_000      # nanoseconds


def event(name, start_us, end_us):
    return NS(name=name, start_ns=start_us * US, duration_ns=(end_us - start_us) * US)


def reduced(host, busy=()):
    """A trace of one chip, busy in the ``(start, end)`` intervals of
    ``busy``, and the host spans ``(name, start, end)`` of ``host``."""
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[event("jit_step(a)", s, e) for s, e in busy]),
        NS(name="XLA Ops", events=[event("%fusion = f32[8] fusion()", s, e) for s, e in busy]),
    ])
    thread = NS(name="python3", events=[event(*h) for h in host])
    return trace_reduce.reduce(NS(planes=[device, NS(name="/host:CPU", lines=[thread])]))


def reader(name):
    return harness.load_module(f"{harness.BENCH_DIR}/metrics/{name}.py", f"span_reader_{name}")


def run_of(trace, new_tokens=4):
    cell = NS(workload={"traffic": {"new_tokens": new_tokens}})
    return harness.RunData(cell, 0.0, 0.0, [], {}, None, trace)


def bring_up(t0, read, unpack, leaves, to_device, warmup):
    """The spans of one bring-up starting at ``t0``: each leaf is
    ``(decompress, dequant, to_host)`` lengths, laid end to end."""
    out, t = [], t0
    for name, d in (("checkpoint/read", read), ("checkpoint/unpack", unpack)):
        out.append((name, t, t + d))
        t += d
    for leaf in leaves:
        for name, d in zip(("checkpoint/decompress", "checkpoint/dequant",
                            "checkpoint/to_host"), leaf):
            out.append((name, t, t + d))
            t += d
    out.append(("bring_up/to_device", t, t + to_device))
    t += to_device
    out.append(("bring_up/warmup", t, t + warmup))
    out.append(("generate", t + 1, t + warmup - 1))
    return [("bring_up", t0, t + warmup)] + out


#: set-up's bring-up (before the window), two in the window, and one cut by
#: the window's end
BRING_UPS = reduced(
    [("bench/window", 100, 1000)]
    + bring_up(10, 5, 5, [(10, 10, 10)], 10, 30)
    + bring_up(100, 20, 10, [(20, 5, 15), (10, 3, 17)], 40, 160)
    + bring_up(500, 10, 5, [(20, 5, 20)], 40, 100)
    + bring_up(950, 10, 5, [(20, 5, 20)], 40, 100))


@pytest.mark.parametrize("name, per_bring_up_us", [
    ("bringup_read_s", (20 + 10 + 10 + 5) / 2),
    ("bringup_decompress_s", (20 + 10 + 20) / 2),
    ("bringup_dequant_s", (5 + 3 + 5) / 2),
    ("bringup_roundtrip_s", (15 + 17 + 40 + 20 + 40) / 2),
    ("bringup_warmup_s", (160 + 100) / 2),
])
def test_bring_up_split_reads_each_phase_per_bring_up_in_the_window(name, per_bring_up_us):
    assert reader(name).read(run_of(BRING_UPS)) == pytest.approx(per_bring_up_us * 1e-6)


def test_decode_useful_share_counts_steps_inside_served_generates():
    """Set-up's warm-up ``generate`` (one step) lies before the window; two
    served requests of 4 tokens take 4 steps each, 3 of them useful."""
    steps = lambda t0: [("generate/decode_step", t0 + 10 * i, t0 + 10 * i + 5)  # noqa: E731
                        for i in range(4)]
    trace = reduced([("bench/window", 100, 1000),
                     ("generate", 10, 90), ("generate/decode_step", 50, 60),
                     ("generate", 200, 300)] + steps(240)
                    + [("generate", 500, 600)] + steps(540))
    assert reader("decode_useful_share").read(run_of(trace, new_tokens=4)) == pytest.approx(75.0)


def test_pending_idle_share_leaves_out_the_scheduler_waiting_for_arrivals():
    """Window 100-1100, chip busy 200-400 and 600-1000: idle 100 + 200 + 100.
    The scheduler waits 100-180 (80 idle) and 550-600 (50 idle); its waits
    before the window and across its end are not read."""
    trace = reduced([("bench/window", 100, 1100), ("schedule/wait_arrival", 20, 90),
                     ("schedule/wait_arrival", 100, 180), ("bench/request", 180, 1000),
                     ("schedule/wait_arrival", 550, 600), ("schedule/wait_arrival", 1050, 1200)],
                    busy=[(200, 400), (600, 1000)])
    assert reader("pending_idle_share").read(run_of(trace)) == pytest.approx(
        100 * (400 - 80 - 50) / 1000)


def test_fleet_to_host_idle_is_per_scan_call():
    """Two calls in the window, each idle 20 + 30 us inside its conversion
    (the scan ends inside it, then the eager finals run); set-up's call
    before the window is not read."""
    trace = reduced([("bench/window", 100, 1100),
                     ("bench/scan_call", 10, 90), ("fleet/to_host", 50, 90),
                     ("bench/scan_call", 100, 500), ("fleet/to_host", 300, 480),
                     ("bench/scan_call", 500, 900), ("fleet/to_host", 700, 880)],
                    busy=[(100, 400), (420, 450), (500, 800), (820, 850)])
    assert reader("fleet_to_host_idle_ms").read(run_of(trace)) == pytest.approx(1e-3 * 100 / 2)


NEW_READERS = ["bringup_read_s", "bringup_decompress_s", "bringup_dequant_s",
               "bringup_roundtrip_s", "bringup_warmup_s", "decode_useful_share",
               "pending_idle_share", "fleet_to_host_idle_ms"]


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_program_without_spans_reads_none(name):
    """The benchmark's own spans alone, as a program without spans leaves
    them, and an untraced run."""
    trace = reduced([("bench/window", 0, 1000), ("bench/bringup", 10, 400),
                     ("bench/request", 400, 600), ("bench/scan_call", 600, 900),
                     ("$array.py:631 _value", 650, 900)], busy=[(100, 200)])
    assert reader(name).read(run_of(trace)) is None
    assert reader(name).read(run_of(None)) is None
