"""Operation and byte counts against hand-worked tiny shapes."""
from __future__ import annotations

import pytest

import bench_tiny  # noqa: F401

from bench import counts, harness, trace_reduce

TINY = dict(num_hidden_layers=2, hidden_size=4, intermediate_size=8, num_attention_heads=2,
            num_key_value_heads=1, head_dim=2, vocab_size=10)
PEAKS = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}


def _reader(name):
    return harness.load_module(f"{harness.BENCH_DIR}/metrics/{name}.py", f"reader_{name}")


def test_prefill_flops_by_hand():
    # per token, per layer: q 4x4, k 4x2, v 4x2, o 4x4, mlp 3 x 4x8
    #   = 16 + 8 + 8 + 16 + 96 = 144 MACs -> 288 operations
    # attention, 3 tokens: 3*4/2 = 6 query-key pairs x 4 x H(2) x D(2) = 96
    # per layer: 3 * 288 + 96 = 960; two layers 1920; head at one position
    # 2 * 4 * 10 = 80 -> 2000 per sequence
    assert counts.prefill_flops(TINY, batch=1, length=3) == 2000
    assert counts.prefill_flops(TINY, batch=5, length=3) == 10000


def test_decode_and_request_flops_by_hand():
    # per layer 288 + 4 * 2 * 2 * context(4) = 352; x2 = 704; + head 80
    assert counts.decode_flops(TINY, batch=1, context=4) == 784
    # a request of 3 prompt tokens and 3 new: prefill + steps at context 4, 5
    assert counts.request_flops(TINY, 1, 3, 3) == 2000 + 784 + 816


def test_dequant_work_and_roofline_by_hand():
    call = trace_reduce.CustomCall(0.0, 2.0, [("bf16", (4, 256))],
                                   [("s8", (4, 256)), ("f32", (4, 2))])
    reader = _reader("dequant_roofline")
    ops, nbytes = reader.work(call)
    assert ops == 1024 and nbytes == 1024 + 32 + 2048
    run = type("Run", (), {"peaks": PEAKS, "trace": type("T", (), {
        "custom_calls": lambda self: [call]})()})()
    # least time = max(1024 / 100, 3104 / 10) = 310.4 s against 2 s spent
    assert reader.read(run) == pytest.approx(100.0 * 310.4 / 2.0)
    other = trace_reduce.CustomCall(0.0, 1.0, [("bf16", (4, 256))], [("bf16", (4, 256))])
    assert reader.work(other) is None


def test_flash_attention_work_by_hand():
    q = ("bf16", (1, 4, 8, 16))
    kv = ("bf16", (1, 2, 8, 16))
    call = trace_reduce.CustomCall(0.0, 1.0, [q], [q, kv, kv])
    ops, nbytes = _reader("flash_attention_roofline").work(call)
    assert ops == 4 * 1 * 4 * 16 * 8 * 9 // 2          # 36 causal pairs per head
    assert nbytes == 2 * (512 + 256 + 256 + 512)


def test_no_trace_reads_nothing():
    run = type("Run", (), {"trace": None, "peaks": PEAKS})()
    for name in ("dequant_roofline", "flash_attention_roofline", "decode_step_ms",
                 "decode_idle_share", "periodic_scan_ms", "device_idle.fleet"):
        assert _reader(name).read(run) is None
