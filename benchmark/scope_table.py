"""One traced run of a cell with the scope tables shown.

    chiprun -- python3 -m benchmark.scope_table --workload <cell> --seed <n> --seconds <s>

`benchmark.run --trace 1` with the logging switched on that the span
readers and the program's tracing write to: the scopes with most device
time, the instructions outside every scope, and the seconds the scope
map's compile took after the window (PERF.md section 5 is made from
these). The result line is the harness's own, last as ever.
"""

from __future__ import annotations

import logging
import sys

from benchmark import run


def main(argv=None) -> int:
    logging.basicConfig(format="%(message)s")
    for name in ("benchmark.span_readers", "proteinbert_tpu.obs.tracing"):
        logging.getLogger(name).setLevel(logging.INFO)
    return run.main([*(sys.argv[1:] if argv is None else argv), "--trace", "1"])


if __name__ == "__main__":
    raise SystemExit(main())
