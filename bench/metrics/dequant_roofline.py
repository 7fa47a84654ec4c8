"""Share of its roofline that the Pallas dequant kernel reaches in the
traced window: the least time the chip needs for the kernel's work (int8
values and float32 scales read, bf16 written; one multiply per element) at
its published peaks, over the kernel's device time.  The kernel is the
Pallas call that takes ``(s8[R, C], f32[R, C/g])`` and returns ``bf16[R, C]``."""


def work(call):
    """(operations, bytes) of one run, or None if it is not this kernel."""
    ops = call.operands
    if (len(ops) != 2 or ops[0][0] != "s8" or ops[1][0] != "f32" or len(call.results) != 1
            or call.results[0][0] != "bf16" or len(ops[0][1]) != 2):
        return None
    (r, c), (_, g) = ops[0][1], ops[1][1]
    return r * c, r * c * 1 + r * g * 4 + r * c * 2


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
