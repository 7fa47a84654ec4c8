"""Set-up: process start to the window (imports, inputs and weights,
checkpoint write, compiles, warm-up), host clock."""


def read(run):
    return run.setup_s
