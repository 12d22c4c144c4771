"""The program's own spans (``repro.obs``), as the metric readers take
them.

Set-up metrics read the spans that start before the window opens
(``run.t0``), engine metrics those that start inside it (``[run.t0,
run.t_end]``).  A reader gets ``None`` where the program keeps no span log
(a program without ``repro.obs``), where the log holds no such span, or
where the bounded log dropped records the reading needs.
"""
from __future__ import annotations


def records(run, part: str):
    """The span log as it bears on ``part`` (``"setup"`` or
    ``"window"``), or None where it cannot be read whole."""
    try:
        from repro import obs
    except ImportError:
        return None
    if run.t0 is None:
        return None
    log = obs.spans()
    if obs.dropped():
        # The log drops the records that closed first.  Set-up spans are
        # among them; a window span is not if the oldest record kept
        # closed before the window opened.
        if part == "setup" or not log or log[0].end >= run.t0:
            return None
    return log


def named(run, part: str, name: str):
    """Spans called ``name`` that start in ``part``, or None if none."""
    log = records(run, part)
    if log is None:
        return None
    if part == "setup":
        found = [s for s in log if s.name == name and s.start < run.t0]
    else:
        found = [s for s in log if s.name == name
                 and run.t0 <= s.start <= run.t_end]
    return found or None


def setup_total_s(run, name: str):
    """Seconds in set-up spans called ``name``, summed."""
    found = named(run, "setup", name)
    return None if found is None else sum(s.end - s.start for s in found)


def window_mean_ms(run, name: str):
    """Mean milliseconds of the window's spans called ``name``."""
    found = named(run, "window", name)
    if found is None:
        return None
    return 1e3 * sum(s.end - s.start for s in found) / len(found)
