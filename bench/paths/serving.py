"""Driver of the served path: ``run_arrival_schedule`` over a
``DutyCycleController`` whose handle is a ``ServingEngine``.

The cell file chooses the controller's strategy and, for ``on_off``, the
checkpoint every bring-up restores:

* ``on_off``: set-up writes the seed's weights as a checkpoint into a
  temporary directory (under ``TMPDIR``, deleted at the end) and runs one
  whole On-Off cycle; in the window every request brings the engine up with
  ``bring_up_from_checkpoint`` and releases it after its tokens;
* ``idle_waiting``: the engine is built from the weights made on the device,
  brought up once in set-up, and warmed with one request of every prompt
  length; in the window it stays resident.

Every request records when it was due, when inference started, the engine's
own ``prefill_s`` and ``decode_s``, and its tokens.  The benchmark's host
spans (``bench/bringup``, ``bench/request``) go into the profiler's trace.
"""
from __future__ import annotations

import itertools
import math
import os
import shutil
import tempfile
import time

import numpy as np

from bench import harness, traffic


def arch_config(c: dict):
    """The program's ``ArchConfig`` from the configuration's published keys."""
    from repro.configs.base import ArchConfig

    if c["model_type"] != "qwen3" or c["hidden_act"] != "silu" or c["attention_bias"]:
        raise harness.BenchError(f"{c['name']}: only the Qwen3 dense decoder is wired here")
    return ArchConfig(
        name=c["name"], family="dense", num_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"], qk_norm=True,
        rope_theta=float(c["rope_theta"]), tie_embeddings=c["tie_word_embeddings"],
        norm_eps=float(c["rms_norm_eps"]), mlp_kind="swiglu",
    )


class _FirstClock:
    """``time.perf_counter`` that remembers its first reading: the schedule's
    own start, against which offsets are due."""

    def __init__(self):
        self.first = None

    def __call__(self) -> float:
        t = time.perf_counter()
        if self.first is None:
            self.first = t
        return t


class Runner:
    def __init__(self, cell, seed: int, seconds: float, devices):
        self.cell = cell
        self.seed = seed
        self.seconds = seconds
        self.device = devices[0]
        self.c = cell.config
        self.wl = cell.workload
        self.tr = self.wl["traffic"]
        self.strategy = self.wl["strategy"]
        self.records: list[dict] = []
        self.spans: dict[str, list] = {"bringup": []}
        self.controller = None
        self.tmpdir = None
        self.window_open = False

    # -- set-up ---------------------------------------------------------
    def setup(self) -> dict:
        import jax

        from repro.checkpoint import CheckpointManager
        from repro.core.duty_cycle import DutyCycleController, PowerModel
        from repro.models import model_zoo as zoo
        from repro.serving.engine import ServingEngine, bring_up_from_checkpoint

        ref = harness.load_module(
            os.path.join(self.cell.bench_dir, "reference", f"{self.c['reference']}.py"),
            "bench_reference_serving")
        self.ref = ref
        split = {}
        t = time.perf_counter()
        self.arch = arch_config(self.c)
        self.new_tokens = int(self.tr["new_tokens"])
        self.max_len = max(self.tr["prompt_lens"]) + self.new_tokens
        closed = self.tr["arrivals"] == "closed_loop"
        pool = math.ceil(self.seconds / 4.0) + 4 if closed else 0
        sched = traffic.schedule(self.tr, self.seed, self.seconds, closed_pool=pool)
        self.offsets = sched["offsets_s"]
        self.lens = sched["prompt_lens"]
        batch = int(self.tr["batch"])
        host_prompts = traffic.prompts(self.lens + sorted(set(self.lens)), batch,
                                       self.c["vocab_size"], self.seed)
        self.host_prompts = host_prompts[: len(self.lens)]
        prompts = jax.device_put(host_prompts, self.device)
        self.prompts, warm = prompts[: len(self.lens)], prompts[len(self.lens):]
        weights = ref.init_weights(self.c, traffic.jax_seed(self.seed), self.device)
        want = zoo.param_shapes(self.arch)
        if jax.tree.structure(weights) != jax.tree.structure(want) or any(
            a.shape != b.shape for a, b in zip(jax.tree.leaves(weights), jax.tree.leaves(want))
        ):
            raise harness.BenchError("the engine's parameter layout differs from the reference's")
        jax.block_until_ready(weights)
        split["inputs_and_weights_s"] = time.perf_counter() - t

        if self.strategy == "on_off":
            t = time.perf_counter()
            self.tmpdir = tempfile.mkdtemp(prefix="bench-ckpt-")
            manager = CheckpointManager(self.tmpdir, keep=1, mode=self.wl["checkpoint"])
            manager.save(0, weights)
            for leaf in jax.tree.leaves(weights):
                leaf.delete()
            del weights
            split["checkpoint_save_s"] = time.perf_counter() - t
            warm_batch = {"tokens": warm[0]}

            def bring_up():
                return bring_up_from_checkpoint(self.arch, manager, self.max_len,
                                                warmup_batch=warm_batch)
        elif self.strategy == "idle_waiting":
            def bring_up():
                return ServingEngine(self.arch, weights, self.max_len)
        else:
            raise harness.BenchError(f"unknown strategy {self.strategy!r}")

        def timed_bring_up():
            from jax.profiler import TraceAnnotation

            t0, w0 = time.perf_counter(), time.time()
            with TraceAnnotation("bench/bringup"):
                engine = bring_up()
            if self.window_open:
                self.spans["bringup"].append((t0, time.perf_counter(), w0, time.time()))
            return engine

        power = PowerModel(config_mw=1.0, infer_mw=1.0, idle_mw=1.0)
        self.controller = DutyCycleController(
            timed_bring_up, self._infer, lambda engine: engine.release(), power,
            strategy=self.strategy)
        t = time.perf_counter()
        # one whole cycle (on_off) or the bring-up and one request of every
        # length (idle_waiting), through the window's own calls
        for w in (warm[:1] if self.strategy == "on_off" else warm):
            self.controller.submit({"tokens": w, "warm": True})
        split["warmup_s"] = time.perf_counter() - t
        return split

    # -- window ---------------------------------------------------------
    def _infer(self, engine, request):
        from jax.profiler import TraceAnnotation

        if request.get("warm"):
            return engine.generate({"tokens": request["tokens"]}, n_new=self.new_tokens)
        i, j = request["i"], request["prompt"]
        t0 = time.perf_counter()
        with TraceAnnotation("bench/request", i=i, prompt_len=self.lens[j]):
            result = engine.generate({"tokens": self.prompts[j]}, n_new=self.new_tokens)
        rec = self.records[i]
        rec.update(t_infer=t0, t_done=time.perf_counter(), prefill_s=result.prefill_s,
                   decode_s=result.decode_s, tokens=result.tokens)
        return result

    def window(self, seconds: float) -> None:
        from repro.serving.scheduler import run_arrival_schedule

        from repro.core.phases import CONFIGURATION

        clock = _FirstClock()
        batch = int(self.tr["batch"])
        n_phases = len(self.controller.records)
        self.window_open = True
        if self.tr["arrivals"] == "closed_loop":
            end = time.perf_counter() + seconds

            def requests():
                # one client: request i is issued (and due) when i - 1 is done
                for i in itertools.count():
                    now = time.perf_counter()
                    if now >= end:
                        return
                    j = i % len(self.lens)
                    self.records.append({"i": i, "prompt": j, "due": now, "batch": batch,
                                         "prompt_len": self.lens[j]})
                    yield {"i": i, "prompt": j}

            run_arrival_schedule(self.controller, requests(), itertools.repeat(0.0),
                                 clock=clock)
        else:
            self.records = [{"i": i, "prompt": i, "batch": batch, "prompt_len": n}
                            for i, n in enumerate(self.lens)]
            run_arrival_schedule(self.controller,
                                 ({"i": i, "prompt": i} for i in range(len(self.lens))),
                                 self.offsets, clock=clock)
            for rec, off in zip(self.records, self.offsets):
                rec["due"] = clock.first + off
        self.window_open = False
        self.spans["configuration"] = [r.wall_s for r in self.controller.records[n_phases:]
                                       if r.name == CONFIGURATION]

    # -- after the window -----------------------------------------------
    def release(self) -> None:
        import jax

        if self.controller is not None and self.controller.handle is not None:
            self.controller.handle.release()
            self.controller.handle = None
        for rec in self.records:
            if "tokens" in rec:
                rec["tokens"] = np.asarray(jax.device_get(rec["tokens"]))
        self.prompts = None
        if self.tmpdir is not None:
            shutil.rmtree(self.tmpdir, ignore_errors=True)
            self.tmpdir = None

    def attempted_failed(self) -> tuple[int, int]:
        done = sum(1 for r in self.records if "tokens" in r)
        return len(self.records), len(self.records) - done

    def check(self, control: str | None = None) -> dict:
        """The widest gap by which a served token's reference logit lies
        below the reference's best, over a sample of finished requests drawn
        from the seed with the longest prompt in it; and requests that never
        finished.  With ``control`` the gap read is that of the token the
        control (the reference one precision lower) puts first at each of
        those positions, compared against the same limit."""
        limits = self.wl["limits"]
        done = [i for i, r in enumerate(self.records) if "tokens" in r]
        unfinished = len(self.records) - len(done)
        checks = {"unfinished_requests": {"value": unfinished, "limit": 0}}
        if done:
            gaps = self.reference_gaps(done, control)
            gap = gaps["control_max_gap" if control else "max_gap"]
        else:
            gap = float("inf")
        checks["max_logit_gap"] = {"value": gap, "limit": limits["max_logit_gap"]}
        return checks

    def reference_gaps(self, done: list[int], control: str | None = None) -> dict:
        k = int(self.wl["check_requests"])
        longest = max(done, key=lambda i: (self.records[i]["prompt_len"], -i))
        picked = [done[j] for j in traffic.sample(len(done), k, self.seed,
                                                  must=[done.index(longest)])]
        weights = self.ref.init_weights(self.c, traffic.jax_seed(self.seed), self.device)
        if self.strategy == "on_off":
            weights = self.ref.quantized(weights, 127, int(self.wl["checkpoint_group"]))
        out = {"max_gap": 0.0, "n_tokens": 0, "requests": picked}
        if control:
            out["control_max_gap"] = 0.0
        for i in picked:
            rec = self.records[i]
            g = self.ref.served_gaps(weights, self.c, self.host_prompts[rec["prompt"]],
                                     rec["tokens"], control=control)
            out["max_gap"] = max(out["max_gap"], g["max_gap"])
            out["n_tokens"] += g["n_tokens"]
            if control:
                out["control_max_gap"] = max(out["control_max_gap"], g["control_max_gap"])
        return out
