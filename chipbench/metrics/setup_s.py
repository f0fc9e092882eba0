"""Seconds from the start of the process to the opening of the window:
imports, graph, inputs, engine build, compiles and warm-up."""


def read(rec):
    return float(rec.setup_s)
