"""Share of its roofline that the Pallas flash-attention kernel reaches in
the traced window.  The kernel is the Pallas call that takes three 4-D
arrays ``q (B, H, Sq, D)``, ``k`` and ``v (B, KVH, Sk, D)`` and returns one
like ``q``.  Its work is what causal attention needs: ``QK^T`` and ``PV``
over the ``Sq (Sq + 1) / 2`` query-key pairs a prefill may see (4 B H D per
pair), and q, k, v and the output read or written once.  The least time is
the larger of operations over the bf16 peak and bytes over the HBM peak."""

_BYTES = {"bf16": 2, "f16": 2, "f32": 4}


def work(call):
    ops, res = call.operands, call.results
    if len(ops) != 3 or len(res) != 1 or any(len(s) != 4 for _, s in ops + res):
        return None
    (b, h, sq, d), (_, kvh, sk, _) = ops[0][1], ops[1][1]
    if sq != sk or res[0][1] != ops[0][1]:
        return None
    flops = 4 * b * h * d * sq * (sq + 1) // 2
    size = lambda t: _BYTES.get(t[0], 4) * t[1][0] * t[1][1] * t[1][2] * t[1][3]  # noqa: E731
    return flops, sum(size(t) for t in ops) + size(res[0])


def read(run):
    if run.trace is None:
        return None
    need = spent = 0.0
    for call in run.trace.custom_calls():
        w = work(call)
        if w is not None:
            need += max(w[0] / run.peaks["bf16_flops_per_s"], w[1] / run.peaks["hbm_bytes_per_s"])
            spent += call.seconds
    return 100.0 * need / spent if spent > 0 else None
