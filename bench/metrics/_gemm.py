"""Shared by the fused-GEMM roofline readers."""

from bench import costs, xtrace


def roofline(ctx, width):
    """Least time over kernel time, summed over every fused-kernel call in
    the window's steps of ``width``. Each call's operations and bytes come
    from the shapes it ran with (bench/costs.gemm_call_cost): a step that
    computes fewer rows is priced at fewer."""
    least = spent = 0.0
    for t in ctx.ticks_of_width(width):
        for mod in t.modules:
            if mod.program != "jit_step":
                continue
            for op in xtrace.kernel_ops(mod, xtrace.GEMM_KERNEL):
                if len(op.shapes) < 3:
                    continue
                ops, byts, bits = costs.gemm_call_cost(op.shapes)
                least += costs.least_time(ops, byts, bits, ctx.peaks)
                spent += op.dur / 1e9
    return 100.0 * least / spent if spent else None
