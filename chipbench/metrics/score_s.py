"""Host seconds scoring the dispatcher's candidates during set-up: the
program's ``repro.dispatch.score`` spans, summed."""
from yard.spans import setup_total_s


def read(run):
    return setup_total_s(run, "repro.dispatch.score")
