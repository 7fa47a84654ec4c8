"""Seconds per bring-up spent tracing, lowering and compiling or loading
programs from the compile cache: the union of JAX's monitoring spans for
those steps that fall inside the window's bring-ups."""


def read(run):
    ups = run.spans.get("bringup", [])           # (t0, t1, wall0, wall1)
    if not ups:
        return None
    spans = sorted((max(s, w0), min(e, w1)) for _, s, e in run.spans["compile"]
                   for _, _, w0, w1 in ups if e > w0 and s < w1)
    total, end = 0.0, float("-inf")
    for s, e in spans:
        if e > end:
            total += e - max(s, end)
            end = e
    return total / len(ups)
