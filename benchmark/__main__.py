"""``python3 -m benchmark``: see ``benchmark.harness``."""

import time

_T0 = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

# the TPU runtime logs to a fixed directory under /tmp unless told
# otherwise; a run writes only inside its checkout and its own TMPDIR
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from benchmark.harness import main  # noqa: E402

sys.exit(main(t0=_T0))
