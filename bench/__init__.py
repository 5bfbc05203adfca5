"""Chip benchmark of the paged SharePrefill serve.

One command runs one cell of ``BENCHMARK.json`` once:

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, traffic mix, cell or metric
lives in a file of its own that the harness finds by name:

    bench/configs/<config>.json   the deployment: model sizes, engine fields,
                                  and its ``family`` (absent: ``dense_gqa``)
    bench/families/<family>.py    the architecture: parameter layout and
                                  fan-in, plain reference, work counts
    bench/traffic/<mix>.json      the traffic parameters
    bench/limits/<cell>.json      the limits of the correctness comparison
    bench/metrics/<metric>.py     one reader per metric, ``read(run)``

The yardstick lives here and nowhere in the program: traffic generation
(``traffic.py``), the weights (``weights.py``), the plain references
(``families/``, built from ``reference.py``) and their low-precision
control, the comparison (``check.py``), the trace reduction
(``xplane.py``), the work counts (``work.py``) and the peaks table
(``peaks.json``).  From the program the benchmark takes only
the system under test (``repro``), its counters and its kernel names.
"""
