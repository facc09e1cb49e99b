"""Benchmark harness — one module per paper claim.

Prints ``name,metric,...`` CSV lines and writes JSON under
benchmarks/results/.  Roofline tables come from the dry-run
(python -m repro.launch.dryrun --all) and are summarized here if present.

    PYTHONPATH=src python -m benchmarks.run [--only convergence,...]
"""

from __future__ import annotations

import argparse
import sys
import time
import traceback

BENCHES = ["convergence", "error_scaling", "breakdown", "geomed_cost",
           "communication", "lm_attack",
           "selection_rules", "gmom_variants", "noniid"]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--only", default=None,
                   help="comma-separated subset of " + ",".join(BENCHES))
    args = p.parse_args(argv)
    selected = args.only.split(",") if args.only else BENCHES

    failures = []
    for name in selected:
        print(f"\n===== benchmark: {name} =====", flush=True)
        t0 = time.time()
        try:
            module = __import__(f"benchmarks.{name}", fromlist=["main"])
            module.main()
            print(f"===== {name} done in {time.time() - t0:.1f}s =====",
                  flush=True)
        except Exception:  # noqa: BLE001
            traceback.print_exc()
            failures.append(name)

    if failures:
        print(f"\nFAILED benchmarks: {failures}")
        return 1
    print("\nall benchmarks passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
