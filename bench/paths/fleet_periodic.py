"""Driver of the fleet scan: ``repro.fleet.step.run_periodic`` called again
and again on one fleet.

Set-up builds the fleet through the program's own constructors
(``ExperimentSpec`` -> ``DeviceSpec`` -> ``FleetParams``, strategies
alternating device by device), draws every device's remaining budget from
the seed on the device, and makes one call to compile.  In the window each
call advances every device through ``horizon_steps`` request periods from
the start and returns host arrays (counts, alive flags, energies, lifetimes
and the per-step admitted counts); the benchmark keeps a sample of each
call's answers and the whole of the last one.
"""
from __future__ import annotations

import os
import time
import types

import numpy as np

from bench import harness, traffic


class Runner:
    def __init__(self, cell, seed: int, seconds: float, devices):
        self.cell = cell
        self.c = cell.config
        self.wl = cell.workload
        self.seed = seed
        self.device = devices[0]
        self.records: list[dict] = []
        self.spans: dict[str, list] = {}
        self.samples: list[dict] = []
        self.last = None

    def _fleet(self):
        import jax
        import jax.numpy as jnp

        from repro.core.phases import Phase, WorkloadItem
        from repro.core.strategies import IdlePowerMethod
        from repro.core.workload import ExperimentSpec, WorkloadSpec
        from repro.fleet import DeviceSpec, FleetParams

        c = self.c
        item = WorkloadItem("exp2", tuple(Phase(n, p, t) for n, p, t in c["phases"]),
                            idle_power_mw=c["idle_power_mw"])
        specs = [
            ExperimentSpec(
                workload=WorkloadSpec(c["energy_budget_mj"], c["request_period_ms"]),
                item=item, strategy_kind=s,
                method=IdlePowerMethod(c["idle_power_method"]),
                powerup_overhead_mj=c["powerup_overhead_mj"],
            )
            for s in c["strategies"]
        ]
        params = FleetParams.from_specs([DeviceSpec.from_experiment(s) for s in specs])
        params = params.tile(int(c["n_devices"]))
        with jax.enable_x64():
            key = jax.device_put(jax.random.key(traffic.jax_seed(self.seed)), self.device)
            u = jax.random.uniform(key, (int(c["n_devices"]),), jnp.float64)
            # (0, B]: 1 - u with u in [0, 1)
            budgets = (1.0 - u) * c["energy_budget_mj"]
        return params.with_budgets(budgets), budgets

    def setup(self) -> dict:
        from repro.fleet import run_periodic

        if self.wl["traffic"]["arrivals"] != "periodic":
            raise harness.BenchError("the fleet scan serves periodic arrivals only")
        split = {}
        t = time.perf_counter()
        self.params, budgets = self._fleet()
        self.budgets = np.asarray(budgets)
        split["fleet_s"] = time.perf_counter() - t
        self.steps = int(self.c["horizon_steps"])
        t = time.perf_counter()
        run_periodic(self.params, self.steps)
        split["warmup_s"] = time.perf_counter() - t
        n = len(self.budgets)
        self.sample_idx = np.asarray(traffic.sample(n, min(n, 4096), self.seed))
        self.run = run_periodic
        return split

    def window(self, seconds: float) -> None:
        from jax.profiler import TraceAnnotation

        end = time.perf_counter() + seconds
        while time.perf_counter() < end:
            t0 = time.perf_counter()
            with TraceAnnotation("bench/scan_call"):
                res = self.run(self.params, self.steps)
            t1 = time.perf_counter()
            i = self.sample_idx
            self.samples.append({"n_items": res.n_items[i], "alive": res.alive[i],
                                 "energy_mj": res.energy_mj[i]})
            self.records.append({"t0": t0, "t1": t1, "devices": len(res.n_items),
                                 "steps": res.n_steps})
            self.last = res

    def release(self) -> None:
        self.params = None

    def attempted_failed(self) -> tuple[int, int]:
        return len(self.records), 0

    def check(self, control: str | None = None) -> dict:
        """Counts, alive flags and per-step admissions exactly; energies and
        ledger axes to a relative limit; against the plain reference, for
        the whole fleet of the last call and a sample of every call.  With
        ``control`` (``"float32"``) the reference one precision below the
        configuration's float64 takes the program's place."""
        ref_mod = harness.load_module(
            os.path.join(self.cell.bench_dir, "reference", f"{self.c['reference']}.py"),
            "bench_reference_fleet")
        ref = ref_mod.simulate(self.c, self.budgets, self.steps)
        if control is not None:
            if control != "float32":
                raise ValueError(control)
            low = ref_mod.simulate(self.c, self.budgets, self.steps, dtype=np.float32)
            ledger = types.SimpleNamespace(**{f"{k}_mj": v for k, v in low["ledger"].items()})
            self.last = types.SimpleNamespace(
                n_items=low["n_items"], alive=low["alive"], energy_mj=low["energy_mj"],
                alive_over_time=low["admitted_per_step"], ledger=lambda: ledger)
            self.samples = []
        return self.compare(ref)

    def compare(self, ref: dict) -> dict:
        limits = self.wl["limits"]
        res = self.last
        e_ref = ref["energy_mj"].astype(np.float64)
        scale = np.maximum(np.abs(e_ref), 1e-300)
        counts = int(np.sum(res.n_items != ref["n_items"]))
        alive = int(np.sum(res.alive != ref["alive"]))
        per_step = int(np.sum(res.alive_over_time != ref["admitted_per_step"]))
        i = self.sample_idx
        for s in self.samples:
            counts += int(np.sum(s["n_items"] != ref["n_items"][i]))
            alive += int(np.sum(s["alive"] != ref["alive"][i]))
        energy = float(np.max(np.abs(res.energy_mj - e_ref) / scale))
        for s in self.samples:
            energy = max(energy, float(np.max(np.abs(s["energy_mj"] - e_ref[i]) / scale[i])))
        led = res.ledger()
        ledger = max(
            float(np.max(np.abs(np.asarray(getattr(led, f"{ax}_mj")) - ref["ledger"][ax]) / scale))
            for ax in ref["ledger"])
        return {
            "count_mismatches": {"value": counts, "limit": 0},
            "alive_mismatches": {"value": alive, "limit": 0},
            "per_step_mismatches": {"value": per_step, "limit": 0},
            "energy_max_rel_err": {"value": energy, "limit": limits["energy_max_rel_err"]},
            "ledger_max_rel_err": {"value": ledger, "limit": limits["ledger_max_rel_err"]},
        }
