"""Record the small profiler trace that ``tests/bench`` checks the trace
reduction against, and print what the trace holds.

    python bench/tools/record_trace.py OUT_DIR

Run it on the machine with the chip.  It traces, under the benchmark's own
host spans, one jitted matrix product, the Pallas dequant kernel, a jitted
two-layer scan around the Pallas flash-attention kernel, and a 50 ms host
sleep with the device idle, then writes ``trace.xplane.pb.gz`` and a JSON
summary of every plane, line and event name into OUT_DIR.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "src"))


def workload():
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    from repro.kernels.dequant.kernel import dequantize_blocked
    from repro.kernels.flash_attention.kernel import flash_attention

    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (1024, 1024), jnp.bfloat16)
    q8 = jax.random.randint(key, (512, 1024), -127, 128, jnp.int32).astype(jnp.int8)
    scales = jnp.full((512, 8), 0.01, jnp.float32)
    qkv = jax.random.normal(key, (2, 3, 2, 256, 2, 128), jnp.bfloat16)

    matmul = jax.jit(lambda x: x @ x)
    dequant = jax.jit(lambda q, s: dequantize_blocked(q, s, group=128))

    def layers(qkv):
        def body(c, x):
            q, k, v = x
            return c + flash_attention(q, k, v, causal=True).sum(), None
        return jax.lax.scan(body, jnp.zeros((), jnp.bfloat16), qkv)[0]

    scan = jax.jit(layers)
    # compile outside the trace
    jax.block_until_ready((matmul(a), dequant(q8, scales), scan(qkv)))
    return a, q8, scales, qkv, matmul, dequant, scan, TraceAnnotation


def main(out_dir: str) -> int:
    import jax
    from jax.profiler import ProfileData

    if jax.devices()[0].platform != "tpu":
        print("record_trace: no TPU", file=sys.stderr)
        return 1
    a, q8, scales, qkv, matmul, dequant, scan, TraceAnnotation = workload()
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        jax.profiler.start_trace(tmp)
        t0 = time.perf_counter()
        with TraceAnnotation("bench/request"):
            matmul(a).block_until_ready()
            dequant(q8, scales).block_until_ready()
        with TraceAnnotation("bench/idle"):
            time.sleep(0.05)
        with TraceAnnotation("bench/request"):
            scan(qkv).block_until_ready()
        t1 = time.perf_counter()
        jax.profiler.stop_trace()
        path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
        os.makedirs(out_dir, exist_ok=True)
        with open(path, "rb") as f, gzip.open(os.path.join(out_dir, "trace.xplane.pb.gz"), "wb") as g:
            shutil.copyfileobj(f, g)
        prof = ProfileData.from_file(path)
        summary = {"window_s": t1 - t0, "device_kind": jax.devices()[0].device_kind,
                   "planes": []}
        for plane in prof.planes:
            p = {"name": plane.name, "stats": {k: str(v) for k, v in plane.stats}, "lines": []}
            for line in plane.lines:
                events = list(line.events)
                names = sorted({e.name for e in events})
                p["lines"].append({
                    "name": line.name, "n_events": len(events), "names": names[:60],
                    "first": [
                        {"name": e.name, "start_ns": e.start_ns, "duration_ns": e.duration_ns,
                         "stats": {k: str(v)[:300] for k, v in e.stats}}
                        for e in events[:12]
                    ],
                })
            summary["planes"].append(p)
        with open(os.path.join(out_dir, "trace_summary.json"), "w") as f:
            json.dump(summary, f, indent=1)
        print(json.dumps({"xplane_bytes": os.path.getsize(path), "window_s": t1 - t0}))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1 else "chiprun_out/trace"))
