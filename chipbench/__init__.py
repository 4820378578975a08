"""On-chip benchmark of PCCL on TPU v5e.

``python chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json``. Everything a cell needs
is found by name: its configuration in ``configs/<config>.json``, its
traffic mix in ``traffic/<traffic>.json`` (which names the generator in
``generators/`` that generates and runs it), and each per-layer metric in
``metrics/<metric>.py``. A cell is added by adding files and a
``BENCHMARK.json`` entry; no file of the harness changes.

The yardstick lives here and nowhere in the program: traffic generation,
the trace reduction (``trace.py``), the peaks table and least-bytes
functions (``peaks.py``), the request-unit conversion (``units.py``) and the
plain references that decide ``correct``.
"""
