"""Share (%) of a request path's total that a listed set of stages
covers: the sum of their ``cubefs_request_stage_seconds`` ``_sum`` over
the window / that of ``over``. The stages are listed, not discovered,
because they have to be disjoint for the sum to mean anything and the
program's registry also holds stages that overlap them (``codec_step``
runs inside ``encode_admission``). A stage the program does not have
adds nothing."""

from .. import registry

METRIC = "cubefs_request_stage_seconds_sum"


def read(cell, path, stages, over="total"):
    whole = registry.total(cell.registry, METRIC, path=path, stage=over)
    if whole <= 0:
        return None
    covered = sum(registry.total(cell.registry, METRIC, path=path, stage=s)
                  for s in stages)
    return 100.0 * covered / whole
