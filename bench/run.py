"""Run one cell of the benchmark once:

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout on the machine that holds the cell's chips.
See `bench/harness/main.py` for what it prints."""
import pathlib
import sys
import time

if __name__ == "__main__":
    t_start = time.monotonic()             # set-up is timed from here
    root = pathlib.Path(__file__).resolve().parent.parent
    sys.path.insert(0, str(root))
    from bench.harness.main import main
    sys.exit(main(sys.argv[1:], t_start, root))
