"""Device milliseconds a round spent in collectives, averaged over the
chips: the summed device time of every all-reduce, all-gather,
reduce-scatter, collective-permute and all-to-all in the window (their
async `-start` and `-done` halves too), over chips and rounds. Nothing to
read without a trace or where no collective ran.

An operation counts by its opcode, never by a name that merely appears
among its operands. On a TPU v5e the dense bank sharded over four chips
(configuration `mlp_sharded_n100k`, traffic `fresh240`) shows one
collective in its window: `%all-reduce.26 = (f32[320,128]{...}, ...,
f32[320,256,128]{...}, ...) all-reduce(...)`, once a round on each chip,
the cohort's 320 rows of every leaf gathered from the row-sharded bank
(each chip contributes the rows it holds, the sum assembles them), and
no async halves or other collective."""
import re

import trace_reduce

# the opcode of a trace event's HLO instruction (`trace_reduce.short_name`)
COLLECTIVE = re.compile(r" (all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all)(-start|-done)?$")


def read(ctx):
    if ctx.events is None:
        return None
    lo, hi = trace_reduce.window_bounds(ctx.events)
    seconds = sum(
        max(0, min(e["start_ns"] + e["dur_ns"], hi) - max(e["start_ns"], lo))
        for e in trace_reduce.device_ops(ctx.events)
        if COLLECTIVE.search(trace_reduce.short_name(e["name"]))) / 1e9
    if seconds <= 0:
        return None
    return 1e3 * seconds / ctx.chips / ctx.rounds
