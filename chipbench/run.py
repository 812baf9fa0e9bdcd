"""Run one benchmark cell once (see ``chipbench/harness.py``).

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Exits non-zero, printing no result, where JAX finds fewer TPU chips than
the cell asks for.
"""

import time

STARTED = time.monotonic()  # set-up is counted from here

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# import the benchmark as a package from the checkout's root, not its files
# from this directory (where they could shadow modules of the same name)
sys.path[0] = str(ROOT)

from chipbench import harness  # noqa: E402

if __name__ == "__main__":
    try:
        harness.main(sys.argv[1:], root=ROOT, started=STARTED)
    except harness.Refused as e:
        print(e.code, file=sys.stderr)
        sys.exit(2)
