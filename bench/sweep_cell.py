"""``bench/sweep.py`` for a serving cell whose driver is not
``drivers/serve.py``.

    python3 bench/sweep_cell.py --workload mellum2-reason --seconds 51 --rates 0.3 0.4

``sweep.py`` loads ``drivers/serve.py`` by name; this runs it with the
driver the cell's configuration names (its ``kind``), which offers the
same surface (``run_window``, ``end_to_end``, ``tracked``, ``engine``).
The benchmark's own runs never run this.
"""

import argparse
import sys

import run
import sweep


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(add_help=False)
    parser.add_argument("--workload", required=True)
    known, _ = parser.parse_known_args(argv)
    kind = run.cell_spec(known.workload)["config"]["kind"]
    load = run.load_module

    def cell_driver(path, name):
        if path == run.BENCH / "drivers" / "serve.py":
            path, name = run.BENCH / "drivers" / f"{kind}.py", f"bench_driver_{kind}"
        return load(path, name)

    run.load_module = cell_driver
    try:
        return sweep.main(argv)
    finally:
        run.load_module = load


if __name__ == "__main__":
    sys.exit(main())
