"""Record ``chipbench/testdata/small.xplane.pb``: a traced run of each
small test cell on the chip, for the trace-reduction tests.

    python chipbench/tests/record_trace.py

Run on a TPU; the two traces go to ``chipbench/testdata/``.
"""

import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))

from chipbench import harness  # noqa: E402
from chipbench.tests import tinyroot  # noqa: E402

if __name__ == "__main__":
    root = tinyroot.make(Path(tempfile.mkdtemp()))
    for cell, name in (("tiny-composite", "composite"),
                       ("tiny-segment", "segment")):
        harness.main(["--workload", cell, "--seed", "1234", "--seconds", "0.2",
                      "--trace", "1"], root=root, started=time.monotonic(),
                     keep_trace=ROOT / "chipbench" / "testdata"
                     / f"{name}.xplane.pb")
