"""On-chip benchmark of the BLMAC filter-bank serving path.

``python3 chipbench/run.py --workload <name> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` on the TPU it is
started on and prints one JSON line.  Everything that defines the
yardstick lives in this directory: the bank design (`design`), the plain
reference (`reference`), traffic generation (`generator`), the drivers
of the served path (`drivers`), the trace reduction (`tracing`), the
roofline work functions and peaks (`roofline`, ``peaks.json``), one
reader per per-layer metric (``metrics/<name>.py``), the float32 control
(`control`) and the knee sweep that fixes an open-loop cell's rate
(`knee`); the last two are run by hand, never by a cell.
"""
