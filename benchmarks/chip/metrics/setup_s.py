"""Seconds from process start to the start of the window: loading,
weight generation, compilation or cache loads, and warm-up."""


def read(run):
    return run.setup_s
