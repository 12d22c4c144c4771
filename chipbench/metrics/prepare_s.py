"""Host seconds converting and packing the chosen layout during set-up:
the program's ``repro.dispatch.prepare`` spans, summed (the transfers
they start are enqueued, not waited for)."""
from yard.spans import setup_total_s


def read(run):
    return setup_total_s(run, "repro.dispatch.prepare")
