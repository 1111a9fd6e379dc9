"""Child script for the launcher's whole-lines test: every rank prints the
same long lines at the same time, as the ranks of a benchmark cell report
after their last barrier. Launched by tests/test_probe.py via

    python -m parsec_tpu.launch -n 4 --cpu tests/_launch_shout.py [lines]
"""
import os
import sys

rank = os.environ.get("PARSEC_TPU_RANK", "?")
for i in range(int(sys.argv[1]) if len(sys.argv) > 1 else 150):
    print(f"RANK {rank} {i} " + "x" * 1500, flush=True)
