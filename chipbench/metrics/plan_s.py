"""Host seconds in ``Dispatcher.plan`` (classify and plan), from the
benchmark's span around the call."""


def read(run):
    return run.setup_parts.get("plan")
