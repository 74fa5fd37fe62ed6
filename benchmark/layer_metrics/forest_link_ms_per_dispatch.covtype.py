"""Kernels: device self time of the ops traced under the scope
``fjt.forest.link`` (inside ``fjt.forest``) per execution of the scoring
program: a softmax chain's link, the softmax over the class sums, the
label's argmax and the gather of its probability. A program without the
scope, or one whose link the chip fuses into an op of another scope,
reports nothing."""
from lib.readers import scope_ms_per_dispatch


def read(ctx):
    return scope_ms_per_dispatch(ctx, "fjt.forest.link")
