"""The chip benchmark of the coded matmul: one cell, one run, one result line.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` on the chips of the machine it is started
on.  Everything here is found by name: a configuration in ``configs/``, a
traffic mix in ``traffic/``, the system a configuration drives in
``systems/`` (``matmul`` where it names none), a metric's reader in
``metrics/``.
"""
