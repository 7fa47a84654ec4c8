"""Mean length of a bring-up in the window: the duty-cycle controller's
CONFIGURATION records (host clock)."""


def read(run):
    recs = run.spans.get("configuration", [])
    return sum(recs) / len(recs) if recs else None
