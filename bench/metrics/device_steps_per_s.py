"""Simulated device-steps the window completed (devices x steps of every
scan call, host conversion of the results included) over its seconds."""


def read(run):
    steps = sum(r["devices"] * r["steps"] for r in run.records)
    return steps / run.window_s if steps else None
