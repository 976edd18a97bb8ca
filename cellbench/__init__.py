"""cellbench — the cell benchmark of the served blob path.

One command runs one cell (one deployment under one traffic mix) once:

    python3 -m cellbench.run --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Everything that belongs to one deployment, one traffic mix or one metric
is a file of its own, found by the name ``BENCHMARK.json`` gives it:

    configs/<config>.json        the deployment (sizes, guarantees, source)
    traffic/<traffic>.json       generator kind + parameters
    generators/<kind>.py         a traffic generator (setup / run / verify)
    end_to_end/<metric>.json     reader + parameters of a client-side metric
    layers/<metric>.json         reader + parameters of a per-layer metric
    readers/<reader>.py          records / registry / trace -> number

A later PR adds a cell, a configuration or a metric by adding files and
``BENCHMARK.json`` entries; it edits none. From the program the
benchmark takes the system under test (``cubefs_tpu``), its stage
histograms and its batcher counters — nothing else. The reference that
decides ``correct`` (reference.py), the trace reduction (devtrace.py),
the table of peaks and the byte/operation counts (roofline.py,
peaks.json) live here.
"""
