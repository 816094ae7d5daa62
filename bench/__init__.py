"""On-chip benchmark of programs compiled by ``omp.compile``.

``BENCHMARK.json`` at the root of the repository names the cells; each
cell is one configuration (``configs/<name>.json``) under one traffic
mix (``traffic/<mix>.json``).  A configuration names its program family
(``programs/<family>.py``) and the plain reference beside it
(``reference/<family>.py``); every metric is read by
``metrics/<metric>.py``.  ``run.py`` is the command.
"""
