"""setup_s: process start to the window's start, seconds (host clock):
imports, device start-up, weights, engine, and the warm-up of every shape
the cell's traffic uses, compiles and compile-cache reads included."""


def read(run):
    return run.setup_s
