"""The benchmark: cells, traffic, per-layer readers and the trace reduction
(see ``run.py``).  Imports nothing of the program except in the workload
files, which hold the user code under test."""
