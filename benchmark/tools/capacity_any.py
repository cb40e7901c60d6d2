"""``tools/capacity`` for a cell of ANY serve driver: the whole supply
queued at the start, completed requests per second over the steady middle.

    python -m benchmark.tools.capacity_any --workload <cell> --seed 1 \
        [--supply-s 30] [--supply-rate 6]

``tools/capacity`` builds its engine through ``drivers/serve.py``, which is
GPT-2's; here the cell's own driver (``workloads/<cell>.json`` names it)
gives ``build_engine`` and ``warm_up``, and the sweep itself is
``capacity.main``, unchanged."""

import argparse
import importlib

from benchmark import run
from benchmark.tools import capacity


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    args, _ = parser.parse_known_args(argv)
    _, cell, _ = run.load_cell(args.workload)
    capacity.serve = importlib.import_module(f"benchmark.drivers.{cell['driver']}")
    capacity.main(argv)


if __name__ == "__main__":
    main()
