"""The block-ELL aggregation kernel's share of its roofline: the least time
its calls in the traced window could take (the larger of operations over
peak FLOP/s and bytes over peak bandwidth, from the staged operands' shapes)
over their device time.  Nothing to read where the kernel did not run once
per layer of every forward."""
from bench import cost, trace_reduce

KERNEL = "spmm_abft"
OP = "spmm_abft_kernel"            # its op in the device trace


def read(run):
    calls = [c for c in run.ctx.kernel_calls if c["kernel"] == KERNEL]
    forwards = run.ctx.counters.get("forwards")
    if run.trace is None or not calls or not forwards:
        return None
    window = run.trace.window("bench.window")
    ns, count = trace_reduce.op_ns(run.trace, window, OP)
    if ns <= 0 or count != forwards * len(calls):
        return None
    least = sum(cost.least_seconds(*cost.spmm_abft_cost(
        c["vals_shape"], c["vals_itemsize"], c["cols_shape"],
        c["cols_itemsize"], c["out_width"]), run.peak)[0] for c in calls)
    return 100.0 * forwards * least / (ns * 1e-9)
