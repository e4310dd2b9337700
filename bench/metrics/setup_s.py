"""Seconds from process start to the start of the measured window:
imports, inputs, building the program's state, warm-up and any
compilation (host clock)."""


def read(ctx):
    return ctx["setup_s"]
