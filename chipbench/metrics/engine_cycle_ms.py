"""Mean milliseconds from one batch's dispatch to the next while the
engine has work: intervals between consecutive ``repro.engine.dispatch``
starts in the window that no ``repro.engine.idle`` span overlaps."""
from yard.spans import named, records


def read(run):
    starts = named(run, "window", "repro.engine.dispatch")
    if starts is None:
        return None
    starts = sorted(s.start for s in starts)
    idle = [s for s in records(run, "window") if s.name == "repro.engine.idle"]
    cycles = [b - a for a, b in zip(starts, starts[1:])
              if not any(s.start < b and s.end > a for s in idle)]
    if not cycles:
        return None
    return 1e3 * sum(cycles) / len(cycles)
