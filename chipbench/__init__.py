"""The chip benchmark of SSH search: harness, yardstick and cell files.

``python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once on the TPU it
is started on and prints one JSON result line.
"""
