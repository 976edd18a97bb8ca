"""A counter's window delta, times ``scale``, per second the program
spent in one stage of a request path over the same window (the ``_sum``
of ``cubefs_request_stage_seconds{path,stage}``): work per second of the
time it was worked on, wherever the window cuts a unit of work. Nothing
where the program has no such counter or stage."""

from .. import registry

SECONDS = "cubefs_request_stage_seconds_sum"


def read(cell, counter, path, stage, scale=1.0):
    seconds = registry.total(cell.registry, SECONDS, path=path, stage=stage)
    amount = registry.total(cell.registry, counter)
    if seconds <= 0 or amount <= 0:
        return None
    return scale * amount / seconds
