"""The tiny traffic files stand for cells whose large PUTs and repair
steps cross malloc's mmap threshold (`ingest-lrc` brings 104 MB back a
step); cut down that far, their arrays never would. The threshold is cut
with them, so a tiny run keeps what such a cell keeps across requests —
the front door's data rows, the engine's result buffers — and reads the
counters that say so."""

import pytest

from cubefs_tpu.utils import hostmem


@pytest.fixture(autouse=True)
def _threshold_cut_to_the_tiny_sizes(monkeypatch):
    monkeypatch.setattr(hostmem, "MALLOC_MMAP_MAX", 0)
