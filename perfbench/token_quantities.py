#!/usr/bin/env python3
"""One run of a token-stream cell that also prints every end-to-end quantity
its entry can give (``perfbench/tokens.py::QUANTITIES``), for the
``benchmark`` PR that sets bounds for the token metrics from measured
spreads: ``perfbench/run.py`` prints only the metrics ``BENCHMARK.json``
lists for the cell.  The run is ``run.py``'s own, untraced; the line before
the last holds the quantities, the last line is the contract's.

    python3 perfbench/token_quantities.py --workload <cell> --seed N --seconds 40

Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perfbench import manifest as manifest_lib  # noqa: E402
from perfbench import run as run_lib  # noqa: E402


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--platform", default="tpu")
    args = p.parse_args(argv)
    manifest = manifest_lib.Manifest(run_lib.ROOT)
    run = run_lib.CellRun(manifest, manifest.cell(args.workload), args.seed, args.seconds, False,
                          platform=args.platform)
    try:
        line = run.run()
        quantities = run.entry.quantities(run)
    except (run_lib.RunFailure, manifest_lib.ManifestError) as e:
        print(f"perfbench: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        run.children.kill_all()
        for name in ("models", "pool", "traces", "program-traces"):
            shutil.rmtree(os.path.join(run.work, name), ignore_errors=True)
    print(json.dumps({"quantities": quantities}), flush=True)
    run_lib.report(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
