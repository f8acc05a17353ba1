"""The paged bank-scatter kernel's share of its HBM roofline: the least
time the bytes it needs take at the chip's published bandwidth, over the
kernel's summed device time in the window."""
import trace_reduce
import workcount

# the paged Pallas kernel's custom calls, one per parameter leaf a round,
# named in the trace after `kernels/bank_scatter._paged_bank_scatter`
KERNEL = r"^%_paged_bank_scatter(_batched)?\."


def read(ctx):
    if ctx.events is None:
        return None
    seconds = trace_reduce.op_seconds(ctx.events, KERNEL) / ctx.chips
    if seconds <= 0:
        return None
    need = workcount.bank_scatter_bytes(ctx.cfg, sum(ctx.n_active),
                                        ctx.rounds)
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / seconds
