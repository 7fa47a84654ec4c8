"""Mean time from a request's due time to the start of its inference
(controller and scheduler), host clock."""


def read(run):
    waits = [r["t_infer"] - r["due"] for r in run.records if "t_infer" in r]
    return 1000.0 * sum(waits) / len(waits) if waits else None
