"""The repository's chip benchmark.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace
<0|1>`` runs one cell of ``BENCHMARK.json`` once on the accelerator it is
started on and prints one JSON result line. Everything a cell needs is
found by name: its model configuration (``bench/configs/<config>.json``),
its traffic mix (``bench/traffic/<traffic>.json``, read by the
generator of its kind: ``bench/traffic.py`` for requests,
``bench/onboarding.py`` for training rows), its engine or roster sizes
and correctness limits (``bench/cells/<cell>.json``), the driver the
traffic names
(``bench/drivers/<driver>.py``) and one reader per per-layer metric
(``bench/metrics/<metric>.py``). Work counts for rooflines live in
``bench/work/``; peaks in ``bench/peaks.json``.
"""
