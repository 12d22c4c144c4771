"""Mean over the engine's batches of the oldest member's wait from
admission to draft (``BatchRecord.queued_s``), in ms."""


def read(run):
    if not run.batches:
        return None
    return 1e3 * sum(q for _, _, q in run.batches) / len(run.batches)
