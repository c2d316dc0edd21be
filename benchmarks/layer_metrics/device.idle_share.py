"""Share of the traced iterations in which no operation ran on the
device: 1 - union of device-op intervals / traced span, in percent, from
the profiler trace (benchmarks/trace_reduce.py). Layer: device. Moves:
train_mrow_iters_per_s."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not trace.get("window_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
