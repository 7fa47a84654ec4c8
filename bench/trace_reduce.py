"""Reduction of a JAX profiler trace (an XPlane file) to what the per-layer
readers need: device busy and idle time, program and operation times, the
Pallas kernels with their operand shapes, and the host span under each idle
gap.

What the trace holds on a TPU (seen on a v5e with JAX 0.9, see
``bench/testdata``): a plane ``/device:TPU:<i>`` per chip with the lines
``XLA Modules`` (one event per program run, named ``jit_<fn>(<hash>)``) and
``XLA Ops`` (one event per HLO operation, named by the operation's whole HLO
text, shapes included; a ``while`` loop's event spans its body's events); and
a plane ``/host:CPU`` with one line per host thread, named after the
thread (``python``, ``python3``, ...).  The thread that drives the window
holds the ``jax.profiler.TraceAnnotation`` spans; every line that holds a
``bench/`` span is read as the host's.  A Pallas call is an operation with
``custom_call_target="tpu_custom_call"`` and carries no kernel name, so
readers recognise their kernel by its operand and result types.

Times are seconds on the trace's clock.  The device's events run about a
millisecond early against the host's (measured on the recorded trace), which
is noise against a window of seconds.
"""
from __future__ import annotations

import dataclasses
import gzip
import re
from typing import Optional

import numpy as np

DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench/window"
#: Operations whose event spans other operations' events.
CONTAINERS = ("while(", "conditional(", " call(")

_SHAPE = re.compile(r"\b(pred|s4|s8|s16|s32|s64|u8|u32|u64|bf16|f16|f32|f64|f8e4m3fn|f8e5m2)"
                    r"\[([\d,]*)\]")


def shapes(text: str) -> list[tuple[str, tuple[int, ...]]]:
    """``(dtype, dims)`` of every array type in an HLO text, in order."""
    return [(dt, tuple(int(x) for x in dims.split(",") if x)) for dt, dims in _SHAPE.findall(text)]


def op_name(text: str) -> str:
    """The operation's name without its ``%`` and numeric suffix."""
    head = text.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


@dataclasses.dataclass(frozen=True)
class CustomCall:
    """One run of a Pallas kernel: its result and operand types."""

    start: float
    end: float
    results: list
    operands: list

    @property
    def seconds(self) -> float:
        return self.end - self.start


def custom_call(text: str) -> Optional[tuple[list, list]]:
    """``(results, operands)`` of a Pallas call's HLO text, else None."""
    if 'custom_call_target="tpu_custom_call"' not in text or "custom-call(" not in text:
        return None
    head, rest = text.split(" = ", 1)[1].split("custom-call(", 1)
    operands = rest.split("), custom_call_target", 1)[0]
    return shapes(head), shapes(operands)


def _merge(starts: np.ndarray, ends: np.ndarray) -> list[tuple[float, float]]:
    order = np.argsort(starts, kind="stable")
    out: list[list[float]] = []
    for s, e in zip(starts[order], ends[order]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([float(s), float(e)])
    return [(s, e) for s, e in out]


def _clip(intervals, t0: float, t1: float) -> list[tuple[float, float]]:
    return [(max(s, t0), min(e, t1)) for s, e in intervals if e > t0 and s < t1]


@dataclasses.dataclass
class Device:
    ops_start: np.ndarray
    ops_end: np.ndarray
    ops_text: list
    modules: list            # (name with its hash, start, end)
    busy: list               # merged op intervals

    def busy_in(self, t0: float, t1: float) -> float:
        return sum(e - s for s, e in _clip(self.busy, t0, t1))


class Reduced:
    """A reduced trace.  ``window`` is the ``bench/window`` host span (or,
    without it, the first to the last ``bench/`` span)."""

    def __init__(self, devices: list[Device], host: list, window: tuple[float, float]):
        self.devices = devices
        self.host = host                 # (name, start, end), sorted by start
        self.window = window

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def busy_s(self) -> float:
        """Seconds with an operation running, averaged over the chips."""
        t0, t1 = self.window
        return float(np.mean([d.busy_in(t0, t1) for d in self.devices]))

    def modules(self, prefix: str, device: int = 0) -> list[tuple[float, float]]:
        """Runs of the programs whose name starts with ``prefix``, in the window."""
        return [(s, e) for _, s, e in self.named_modules(prefix, device)]

    def named_modules(self, prefix: str = "", device: int = 0) -> list[tuple[str, float, float]]:
        """``(name, start, end)`` of every run in the window of a program whose
        name starts with ``prefix``.  The name is the trace's whole program
        name, ``jit_<fn>(<hash>)``: two programs of one function at other
        shapes differ in their hash."""
        t0, t1 = self.window
        return [(n, s, e) for n, s, e in self.devices[device].modules
                if n.startswith(prefix) and s >= t0 and e <= t1]

    def custom_calls(self, device: int = 0) -> list[CustomCall]:
        """Every Pallas kernel run in the window."""
        d = self.devices[device]
        t0, t1 = self.window
        out = []
        for s, e, text in zip(d.ops_start, d.ops_end, d.ops_text):
            if s < t0 or e > t1:
                continue
            cc = custom_call(text)
            if cc is not None:
                out.append(CustomCall(float(s), float(e), cc[0], cc[1]))
        return out

    def gaps(self, device: int = 0, within: Optional[tuple[float, float]] = None):
        """Idle intervals of one chip inside ``within`` (default: the window)."""
        t0, t1 = within or self.window
        busy = _clip(self.devices[device].busy, t0, t1)
        edges = [t0] + [x for iv in busy for x in iv] + [t1]
        return [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]

    def host_at(self, t: float) -> str:
        """What the host was doing at ``t``: the innermost ``bench/`` span
        and the innermost span of any kind (the latest started of each)."""
        bench, inner = "", "no span"
        for name, s, e in self.host:
            if s > t:
                break
            if e >= t and name != WINDOW_SPAN:
                inner = name
                if name.startswith("bench/"):
                    bench = name
        return inner if bench in ("", inner) else f"{bench} > {inner}"

    def breakdown(self, top: int = 10) -> dict:
        """The operations that took most device time (by program and
        operation name; loops are left out, their bodies are counted) and
        the longest idle gaps, named by the host span under each."""
        t0, t1 = self.window
        total: dict[str, float] = {}
        for d in self.devices[:1]:
            mod_start = np.array([s for _, s, _ in d.modules])
            mod_name = [n.split("(", 1)[0] for n, _, _ in d.modules]
            for s, e, text in zip(d.ops_start, d.ops_end, d.ops_text):
                if s < t0 or e > t1 or any(c in text for c in CONTAINERS):
                    continue
                i = int(np.searchsorted(mod_start, s, side="right")) - 1
                module = mod_name[i] if i >= 0 else "?"
                cc = custom_call(text)
                name = op_name(text)
                if cc is not None:
                    name = "pallas " + ",".join(dt for dt, _ in cc[1]) + "->" + \
                        ",".join(dt for dt, _ in cc[0])
                key = f"{module}/{name}"
                total[key] = total.get(key, 0.0) + float(e - s)
        ops = sorted(total.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gaps(), key=lambda g: g[0] - g[1])[:top]
        return {
            "device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[self.host_at((a + b) / 2), b - a] for a, b in gaps],
        }


def load(path: str):
    """The ``ProfileData`` of an ``.xplane.pb`` file (gzipped or not)."""
    from jax.profiler import ProfileData

    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        return ProfileData.from_serialized_xspace(f.read())


def reduce(prof, n_devices: int = 1, window: Optional[tuple[float, float]] = None) -> Reduced:
    devices: dict[str, Device] = {}
    host: list[tuple[str, float, float]] = []
    for plane in prof.planes:
        if plane.name.startswith(DEVICE_PLANE):
            starts, ends, texts, modules = [], [], [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for e in line.events:
                        s = e.start_ns * 1e-9
                        starts.append(s)
                        ends.append(s + e.duration_ns * 1e-9)
                        texts.append(e.name)
                elif line.name == MODULES_LINE:
                    for e in line.events:
                        s = e.start_ns * 1e-9
                        modules.append((e.name, s, s + e.duration_ns * 1e-9))
            st, en = np.asarray(starts, float), np.asarray(ends, float)
            modules.sort(key=lambda m: m[1])
            devices[plane.name] = Device(st, en, texts, modules, _merge(st, en))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                events = [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                          for e in line.events]
                if any(name.startswith("bench/") for name, _, _ in events):
                    host.extend(events)
    host.sort(key=lambda h: h[1])
    chips = [devices[k] for k in sorted(devices, key=lambda n: int(n.rsplit(":", 1)[1]))]
    if len(chips) < n_devices:
        raise ValueError(f"the trace holds {len(chips)} TPU planes, expected {n_devices}")
    windows = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
    if window is not None:
        pass
    elif windows:
        window = windows[0]
    else:
        spans = [(s, e) for n, s, e in host if n.startswith("bench/")]
        window = (min(s for s, _ in spans), max(e for _, e in spans))
    return Reduced(chips[:n_devices], host, window)


def reduce_file(path: str, n_devices: int = 1) -> Reduced:
    return reduce(load(path), n_devices)
