"""Host seconds in ``sparse.plan(...)`` after planning, until the packed
layout is on the device (conversion, packing, transfer)."""


def read(run):
    return run.setup_parts.get("pack")
