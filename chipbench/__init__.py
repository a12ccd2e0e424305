"""On-chip benchmark of the serving stack: one command, cells found by name.

``python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` serves one cell of ``BENCHMARK.json`` on the TPU it runs
on.  Configurations (``configs/<name>.json``), traffic mixes
(``traffic/<name>.json``), per-layer metrics (``metrics/<name>.py``) and
plain references (``reference/<family>.py``) are files of their own,
found by the names ``BENCHMARK.json`` gives.
"""
