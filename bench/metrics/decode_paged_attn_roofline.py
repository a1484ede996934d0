"""Attention kernel (kernels/flash_paged.py): roofline share of paged
attention in width-1 (decode-only) steps. The least time of each step is, per
layer, the larger of the attention operations over the bf16 peak and the
bytes of the KV pages the scheduled rows' live lengths span (read once, plus
queries and outputs) over the memory bandwidth; it is summed over the steps
and divided by the kernel's device time in them."""

from bench import costs, xtrace


def read(ctx):
    layers = ctx.config["num_hidden_layers"]
    bs = ctx.config["kv"]["block_size"]
    least = spent = 0.0
    for t in ctx.ticks_of_width(1):
        mods = [m for m in t.modules if m.program == "jit_step"]
        if len(mods) != 1 or not t.rows:
            continue
        if xtrace.kernel_calls(mods[0], xtrace.ATTN_KERNEL) != layers:
            continue
        ops, byts = costs.attn_cost(ctx.config, t.rows, bs)
        least += layers * costs.least_time(ops, byts, 16, ctx.peaks)
        spent += xtrace.kernel_ns(mods[0], xtrace.ATTN_KERNEL) / 1e9
    return 100.0 * least / spent if spent else None
