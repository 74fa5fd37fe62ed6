"""Kernels: device self time of the ops traced under the scope
``fjt.forest.leaves`` (inside ``fjt.forest``) per execution of the
scoring program: the XLA rank-wire twin's contraction of the leaf
one-hot with the bf16 hi+lo leaf values, and a chain's logistic link.
The Pallas kernel names no such scope."""
from lib.readers import scope_ms_per_dispatch


def read(ctx):
    return scope_ms_per_dispatch(ctx, "fjt.forest.leaves")
