"""Mean request columns per engine batch (``BatchRecord.widths`` summed;
padding to the launch width left out)."""


def read(run):
    if not run.batches:
        return None
    return sum(c for c, _, _ in run.batches) / len(run.batches)
