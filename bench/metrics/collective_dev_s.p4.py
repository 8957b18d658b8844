"""Device seconds of the collectives per distributed count on the
slowest chip: on each chip's plane in the traced window, the ops named
for a collective, the largest plane, over the counts made in it.

The compiled program names a collective either by its HLO opcode
(``all-reduce.11``, ``all-gather-start.2``, ``collective-permute``) or
after the JAX primitive it came from (``pmax.42``, ``all_to_all.13``,
``psum``); both count.  A collective that the compiler fused into
another op keeps no such name and is not counted."""

from bench import chips, tracing

OPS = (r"^(all-to-all|all-gather|all-reduce|collective-permute"
       r"|all_to_all|all_gather|ppermute|psum(_invariant)?|pmax|pmin)"
       r"(-start|-done|\.\d+)*$")


def read(ctx):
    s = chips.slowest_s(ctx.trace, OPS, line=tracing.OPS_LINE)
    return s / ctx.counters["counts"] if s > 0 else None
