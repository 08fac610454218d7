"""Set-up seconds: process start to the window's opening (weights, warm-up
compiles or cache loads, and, for a closed batch, filling the slots)."""


def read(ctx):
    return ctx["setup_s"]
