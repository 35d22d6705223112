"""The chip benchmark of the partitioner: ``python3 bench/run.py --workload
<cell> --seed <n> --seconds <s> --trace <0|1>`` (see ``harness.py``)."""
