"""The program's layer spans (``jax.profiler.TraceAnnotation``), recorded
by the JAX profiler on the CPU: a 2-layer bring-up from a ``zstd+int8``
checkpoint with warm-up, one ``generate``, a two-request arrival schedule
and a small periodic fleet scan.  The benchmark's readers
(``bench/metrics``) rely on these names, their nesting and their counts."""
from __future__ import annotations

import dataclasses
import glob
import os
from collections import Counter

import jax
import jax.numpy as jnp
import msgpack
import pytest

from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.core import energy_model as em
from repro.core.duty_cycle import DutyCycleController, PowerModel
from repro.core.phases import paper_lstm_item
from repro.fleet import DeviceSpec, FleetParams, run_periodic
from repro.models import model_zoo as zoo
from repro.serving.engine import bring_up_from_checkpoint
from repro.serving.scheduler import run_arrival_schedule

SPANS = ("bring_up", "checkpoint/read", "checkpoint/unpack", "checkpoint/decompress",
         "checkpoint/dequant", "bring_up/to_device", "bring_up/warmup",
         "generate", "generate/decode_step", "schedule/wait_arrival", "fleet/to_host")
N_NEW = 16


def host_spans(logdir: str) -> list[tuple[str, int, int]]:
    """``(name, start_ns, end_ns)`` of every host event named in SPANS, and
    of any other ``checkpoint/`` or ``bring_up/`` span, so that a bring-up
    phase the program no longer opens (``checkpoint/to_host``) would show."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events
                        if e.name in SPANS or e.name.startswith(("checkpoint/", "bring_up/"))]
    return sorted(out, key=lambda s: s[1])


def inside(spans, outer):
    return [s for s in spans if s[1] >= outer[1] and s[2] <= outer[2]]


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """Run every spanned path once under the profiler; returns the spans,
    the checkpoint's leaf records, the engine and a prompt batch."""
    # wide enough that the projections and the embedding are int8-quantized
    cfg = dataclasses.replace(get_config("qwen3-1.7b", reduced=True), d_model=256, d_ff=256,
                              vocab_size=512, num_heads=4, num_kv_heads=2, head_dim=64)
    manager = CheckpointManager(str(tmp_path_factory.mktemp("ckpt")), mode="zstd+int8")
    path = manager.save(0, zoo.init_params(cfg, jax.random.PRNGKey(0)))
    with open(path, "rb") as f:
        leaves = msgpack.unpackb(f.read(), raw=False)["leaves"]
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size,
                                          jnp.int32)}
    fleet = FleetParams.from_specs([
        DeviceSpec(item=paper_lstm_item(), strategy=s, request_period_ms=40.0,
                   e_budget_mj=2500.0, powerup_overhead_mj=em.CALIBRATED_POWERUP_OVERHEAD_MJ)
        for s in ("idle_waiting", "on_off")])
    run_periodic(fleet, 64)                 # compiled before the trace
    logdir = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(logdir):
        engine = bring_up_from_checkpoint(cfg, manager, max_len=48, warmup_batch=batch)
        engine.generate(batch, n_new=N_NEW)
        controller = DutyCycleController(
            lambda: engine, lambda eng, x: eng.generate(x, n_new=N_NEW), lambda eng: None,
            PowerModel(config_mw=1.0, infer_mw=1.0, idle_mw=1.0), strategy="idle_waiting")
        run_arrival_schedule(controller, [batch, batch], [0.0, 0.3])
        run_periodic(fleet, 64)
    return host_spans(logdir), leaves, engine, batch


def test_every_span_is_recorded(recorded):
    spans = recorded[0]
    assert set(SPANS) <= {n for n, _, _ in spans}


def test_bring_up_holds_its_phases(recorded):
    """One bring-up: one read and unpack, one decompress per leaf, one
    dequant per quantized leaf (whose result stays on the device, so no
    ``checkpoint/to_host``), then the upload and the warm-up, whose
    ``generate`` takes one decode step."""
    spans, leaves = recorded[:2]
    (up,) = [s for s in spans if s[0] == "bring_up"]
    counts = Counter(n for n, _, _ in inside(spans, up))
    n_quant = sum("quant" in leaf for leaf in leaves)
    assert 0 < n_quant < len(leaves)
    assert counts == {"bring_up": 1, "checkpoint/read": 1, "checkpoint/unpack": 1,
                      "checkpoint/decompress": len(leaves), "checkpoint/dequant": n_quant,
                      "bring_up/to_device": 1,
                      "bring_up/warmup": 1, "generate": 1, "generate/decode_step": 1}
    every = Counter(n for n, _, _ in spans)
    assert every["checkpoint/dequant"] == n_quant      # none outside the bring-up
    (warmup,) = [s for s in spans if s[0] == "bring_up/warmup"]
    assert [n for n, _, _ in inside(spans, warmup)] == [
        "bring_up/warmup", "generate", "generate/decode_step"]


def test_each_generate_holds_one_span_per_decode_step(recorded):
    """The warm-up's one step, then the direct call's and the two scheduled
    requests' N_NEW each; every step lies inside a ``generate``."""
    spans = recorded[0]
    gens = [s for s in spans if s[0] == "generate"]
    per_generate = [sum(n == "generate/decode_step" for n, _, _ in inside(spans, g))
                    for g in gens]
    assert per_generate == [1, N_NEW, N_NEW, N_NEW]
    assert sum(per_generate) == sum(n == "generate/decode_step" for n, _, _ in spans)


def test_scheduler_and_fleet_spans(recorded):
    """One wait per scheduled request, the second lasting until 0.3 s after
    the schedule's start and holding no ``generate``; one host conversion
    per fleet call."""
    spans = recorded[0]
    waits = [s for s in spans if s[0] == "schedule/wait_arrival"]
    assert len(waits) == 2
    assert waits[1][2] - waits[0][1] >= 0.29e9
    assert [n for n, _, _ in inside(spans, waits[1])] == ["schedule/wait_arrival"]
    assert sum(n == "fleet/to_host" for n, _, _ in spans) == 1


def test_engine_programs_are_named_after_their_functions(recorded):
    """The device trace names a program after the jitted function:
    ``jit_prefill_fn`` and ``jit_decode_fn``, not ``jit__unknown``."""
    _, _, engine, batch = recorded
    prefill = engine._prefill.lower(engine.params, batch)
    assert "@jit_prefill_fn" in prefill.as_text()
    logits, state = engine._prefill(engine.params, batch)
    token = jnp.argmax(logits, -1).astype(jnp.int32)
    assert "@jit_decode_fn" in engine._decode.lower(engine.params, state, token).as_text()
