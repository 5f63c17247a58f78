"""The chip benchmark of the BFLC system: committee rounds and serving.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell once.  Everything a cell needs is found by name: the cell in
``workloads/``, its configuration and plain reference in ``configs/``, its
traffic mix and generator in ``traffic/``, the entry point it drives in
``drivers/`` and each metric's reader in ``metrics/``.
"""
