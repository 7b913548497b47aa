"""Process start until the first measured request may be sent: weights,
compile or cache load, warm-up (the clock of benchmark/run.py)."""


def read(ctx):
    return ctx["setup_s"]
