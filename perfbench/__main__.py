import time

_T0 = time.monotonic()

import sys  # noqa: E402

from perfbench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t0=_T0))
