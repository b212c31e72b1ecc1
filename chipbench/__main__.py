import time

_T_START = time.perf_counter()  # set-up is counted from here: before any import

import sys  # noqa: E402

from chipbench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], t_start=_T_START))
