"""Chip benchmark of the MEC convolution engine.

``python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once on the TPU it is
started on and prints one JSON result line.  Everything a cell needs is
found by name: ``configs/<config>.json`` (sizes) beside
``configs/<config>.py`` (the plain float32 reference),
``systems/<system>.py`` (what the config's ``system`` runs, with its
control, faults and test size), ``traffic/<mix>.json`` (the load) and
``metrics/<metric>.py`` (one per-layer reader each).
"""
