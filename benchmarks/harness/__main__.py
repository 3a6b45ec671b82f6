import time

T_PROCESS = time.perf_counter()

import sys  # noqa: E402

from .run import main  # noqa: E402

sys.exit(main(t_process=T_PROCESS))
