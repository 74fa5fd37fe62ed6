"""Process start to the first measured record: model generation, parse,
compile or cache load, table allocation and its first copy to the
device, log build, warm-up stream, the reference check, settling."""


def read(ctx):
    return ctx["setup_s"]
