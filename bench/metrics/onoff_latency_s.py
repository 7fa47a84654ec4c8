"""Mean time from a request's arrival to its last token, over every request
the window finished (host clock)."""


def read(run):
    lat = [r["t_done"] - r["due"] for r in run.records if "t_done" in r]
    return sum(lat) / len(lat) if lat else None
