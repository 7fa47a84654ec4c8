"""Plain reference of a periodic duty-cycled fleet (the paper's Eq. 1-3).

Each device receives one request per period.  Its workload item is a list
of phases (power in mW, time in ms); energy in mJ is power x time / 1000.

* On-Off pays the whole item, configuration included, plus the power-up
  overhead for every request: cum(n) = n * E_item, with E_item = sum of all
  phase energies + overhead.
* Idle-Waiting configures once (E_init = configuration energy + overhead),
  then pays the execution phases per request and idles at P_idle for the
  rest of each period: cum(n) = E_init + n * E_exec + (n - 1) * E_idle with
  E_idle = P_idle * (period - execution time) / 1000.

A device admits request n while cum(n) <= budget + eps * (E_item + E_idle)
(E_idle = 0 for On-Off); after its first refusal it admits nothing.  Over a
horizon of H periods it reports the requests admitted, whether it is still
admitting, its energy cum(n_admitted), and the ledger of where the energy
went (configure, compute, idle, overhead).  Per step the fleet reports how
many devices admitted a request.

cum(n) grows with n, so the admitted count is the largest n <= H with
cum(n) within the limit, found here by bisection, in numpy, with no code of
the program.  ``dtype`` float32 gives the control: the same semantics one
precision below the float64 the configuration states.
"""
from __future__ import annotations

import numpy as np


def constants(c: dict, dtype=np.float64) -> dict:
    """Per-strategy constants from the configuration's phases."""
    phases = {name: (p, t) for name, p, t in c["phases"]}
    e = {name: p * t / 1000.0 for name, (p, t) in phases.items()}
    e_cfg = e["configuration"]
    e_all = sum(e.values())
    e_exec = sum(v for k, v in e.items() if k != "configuration")
    t_exec = sum(t for k, (_, t) in phases.items() if k != "configuration")
    t_all = sum(t for _, t in phases.values())
    ovh = c["powerup_overhead_mj"]
    period = c["request_period_ms"]
    out = {}
    for s in c["strategies"]:
        if s == "on_off":
            out[s] = dict(on_off=True, feasible=period >= t_all, e_item=e_all + ovh,
                          e_init=0.0, e_idle=0.0, cfg_pure=e_cfg, ovh=ovh, e_exec=e_exec)
        elif s == "idle_waiting":
            feasible = period >= t_exec
            e_idle = c["idle_power_mw"] * (period - t_exec) / 1000.0 if feasible else 0.0
            out[s] = dict(on_off=False, feasible=feasible, e_item=e_exec, e_init=e_cfg + ovh,
                          e_idle=e_idle, cfg_pure=e_cfg, ovh=ovh, e_exec=e_exec)
        else:
            raise ValueError(f"unknown strategy {s!r}")
    return {s: {k: (dtype(v) if isinstance(v, float) else v) for k, v in d.items()}
            for s, d in out.items()}


def _cum(n, k: dict, dtype):
    nf = n.astype(dtype)
    if k["on_off"]:
        return nf * k["e_item"]
    return k["e_init"] + nf * k["e_item"] + (nf - dtype(1.0)) * k["e_idle"]


def simulate(c: dict, budgets_mj: np.ndarray, horizon: int, dtype=np.float64) -> dict:
    """The fleet after ``horizon`` periods; device ``i`` runs strategy
    ``c["strategies"][i % len(strategies)]`` with budget ``budgets_mj[i]``."""
    strategies = c["strategies"]
    n_dev = len(budgets_mj)
    kind = np.arange(n_dev) % len(strategies)
    budgets = np.asarray(budgets_mj, dtype)
    eps = dtype(c["floor_eps"])
    n = np.zeros(n_dev, np.int64)
    energy = np.zeros(n_dev, dtype)
    ledger = {a: np.zeros(n_dev, dtype) for a in ("configure", "compute", "idle", "overhead")}
    for j, s in enumerate(strategies):
        k = constants(c, dtype)[s]
        sel = kind == j
        if not k["feasible"]:
            continue
        limit = budgets[sel] + eps * (k["e_item"] + k["e_idle"])
        lo = np.zeros(sel.sum(), np.int64)          # cum(lo) <= limit (lo = 0 always)
        hi = np.full(sel.sum(), horizon + 1, np.int64)   # first n known to fail
        while np.any(hi - lo > 1):
            mid = (lo + hi) // 2
            ok = _cum(mid, k, dtype) <= limit
            lo = np.where(ok, mid, lo)
            hi = np.where(ok, hi, mid)
        ns = np.minimum(lo, horizon)
        n[sel] = ns
        nf = ns.astype(dtype)
        any_ = (ns > 0).astype(dtype)
        energy[sel] = np.where(ns > 0, _cum(ns, k, dtype), dtype(0.0))
        n_cfg = nf if k["on_off"] else any_
        ledger["configure"][sel] = n_cfg * k["cfg_pure"]
        ledger["overhead"][sel] = n_cfg * k["ovh"]
        ledger["compute"][sel] = nf * k["e_exec"]
        ledger["idle"][sel] = any_ * (nf - dtype(1.0)) * k["e_idle"]
    alive = n >= horizon
    # a device admits at step t (0-based) iff it admitted more than t requests
    ends = np.bincount(n, minlength=horizon + 1)
    admitted_per_step = (n_dev - np.cumsum(ends))[:horizon]
    return {"n_items": n, "alive": alive, "energy_mj": energy, "ledger": ledger,
            "admitted_per_step": admitted_per_step}
