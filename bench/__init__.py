"""The chip benchmark: one entry point (``bench/run.py``) driven by data.

``BENCHMARK.json`` at the repository root names the metrics, the model
configurations and the cells.  Everything that belongs to one of them
sits in a file of its own, found by its name:

  configs/<config>.json     sizes of one model configuration, its
                            source, what was cut or assumed, and which
                            plain reference in ``reference/`` runs it
  traffic/<traffic>.json    the parameters of one federated job (the
                            generator in ``traffic.py`` reads them)
  cells/<cell>.json         the limits of the correctness comparison of
                            one cell, with the readings they were set from
  metrics/<metric>.py       the reader of one per-layer metric
  reference/<family>.py     a plain reference of one model family

Only the runner (``run.py``), the peaks table (``peaks.py``), the trace
reader (``xtrace.py``), the traffic generator (``traffic.py``) and the
comparison (``compare.py``) are shared.
"""
