"""Seconds from process start to the first timed call: imports, device
init, operator generation, plan, pack, transfer, compile or compile-cache
load, inputs and warm-up."""


def read(run):
    return run.setup_s
