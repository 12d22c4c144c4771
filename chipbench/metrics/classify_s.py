"""Host seconds in the structure classifier during set-up: the program's
``repro.dispatch.classify`` spans, summed."""
from yard.spans import setup_total_s


def read(run):
    return setup_total_s(run, "repro.dispatch.classify")
