"""Idle share of the device in the traced window: ``100 * (1 - busy /
window)``, busy the union of device-op intervals."""
from yard.readers import idle_pct


def read(run):
    return idle_pct(run) if run.calls else None
