"""Checkpoint manager: atomic rotation, async writes, elastic restore.

Fault-tolerance contract (DESIGN.md §6):
  * saves are atomic (tmp + rename) — a crash mid-write never corrupts the
    latest checkpoint;
  * ``restore_latest`` ignores partial files, so restart-after-failure
    always finds the newest complete step;
  * the serialized format is mesh-agnostic: restoring onto a different
    mesh shape (elastic scale up/down) is ``restore + device_put`` with the
    new shardings (tests/test_distributed_multidev.py proves bit-equality
    across re-meshes).
"""
from __future__ import annotations

import os
import re
import threading
from typing import Any, Callable, Optional

import jax
from jax.profiler import TraceAnnotation

from repro.checkpoint import serializer

_CKPT_RE = re.compile(r"^step_(\d+)\.ckpt$")


class CheckpointManager:
    def __init__(self, directory: str, keep: int = 3, mode: str = "zstd"):
        self.directory = directory
        self.keep = keep
        self.mode = mode
        os.makedirs(directory, exist_ok=True)

    # ---- paths ----
    def _path(self, step: int) -> str:
        return os.path.join(self.directory, f"step_{step}.ckpt")

    def steps(self) -> list[int]:
        out = []
        for name in os.listdir(self.directory):
            m = _CKPT_RE.match(name)
            if m:
                out.append(int(m.group(1)))
        return sorted(out)

    # ---- save / restore ----
    def save(self, step: int, state: Any) -> str:
        data = serializer.serialize(state, mode=self.mode)
        path = self._path(step)
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            f.write(data)
            f.flush()
            os.fsync(f.fileno())
        os.rename(tmp, path)          # atomic publish
        self._rotate()
        return path

    def restore(self, step: int, target: Any = None) -> Any:
        with TraceAnnotation("checkpoint/read"), open(self._path(step), "rb") as f:
            data = f.read()
        return serializer.deserialize(data, target)

    def restore_latest(self, target: Any = None) -> tuple[Optional[int], Any]:
        steps = self.steps()
        if not steps:
            return None, None
        step = steps[-1]
        return step, self.restore(step, target)

    def _rotate(self) -> None:
        steps = self.steps()
        for s in steps[: max(0, len(steps) - self.keep)]:
            try:
                os.remove(self._path(s))
            except OSError:
                pass


class AsyncCheckpointer:
    """Snapshot-to-host synchronously, serialize+write on a background
    thread — the train loop never blocks on disk (overlap of checkpoint IO
    with compute, the standard large-scale pattern)."""

    def __init__(self, manager: CheckpointManager):
        self.manager = manager
        self._thread: Optional[threading.Thread] = None
        self.last_error: Optional[Exception] = None

    def save(self, step: int, state: Any) -> None:
        self.wait()
        host_state = jax.tree.map(lambda x: jax.device_get(x), state)

        def _write():
            try:
                self.manager.save(step, host_state)
            except Exception as e:  # noqa: BLE001 — surfaced via last_error
                self.last_error = e

        self._thread = threading.Thread(target=_write, daemon=True)
        self._thread.start()

    def wait(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self.last_error is not None:
            err, self.last_error = self.last_error, None
            raise err
