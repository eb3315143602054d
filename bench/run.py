"""Run one benchmark cell on the chip and print its result line.

  python3 bench/run.py --workload sc2.code.shared --seed 7 --seconds 30 \
      --trace 0

Exits non-zero, printing no result, when JAX finds no TPU, fewer chips
than the cell asks for, or no program beside the benchmark.  The last
line of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (``breakdown`` with ``--trace 1``)
and, last, ``checks``: each number compared with its limit.
"""
import time

T_START = time.perf_counter()            # set-up is counted from here

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="also read the float8 control's gap (calibration)")
    args = ap.parse_args()
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no program under {ROOT / 'src'}", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

    import jax
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    # small eager programs are cached too, so later runs load, not compile
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    from bench import harness
    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), t_start=T_START,
                                  control=args.control)
    except harness.NoChip as e:
        print(f"bench: {e}; nothing run", file=sys.stderr)
        return 2
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
