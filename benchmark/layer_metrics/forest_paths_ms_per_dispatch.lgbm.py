"""Kernels: device self time of the ops traced under the scope
``fjt.forest.paths`` (inside ``fjt.forest``) per execution of the
scoring program: the XLA rank-wire twin's contraction of the compares
with the packed path matrix, and the match of each leaf's path length
(``[B, T, L]``). The Pallas kernel names no such scope."""
from lib.readers import scope_ms_per_dispatch


def read(ctx):
    return scope_ms_per_dispatch(ctx, "fjt.forest.paths")
