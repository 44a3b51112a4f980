"""Run one benchmark cell once, on the chip.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. ``--trace 0`` prints the cell's end-to-end
metrics; ``--trace 1`` records a device trace of the window's end and
prints the per-layer metrics. Either way the run checks the requests it
served against the plain reference (``bench/check.py``) and prints each
number compared beside its limit, as the last lines of standard error and
under ``checks`` in the result. The result is the last line of standard
output, one JSON object.

Set-up counts as ``setup_s``: from the process's start to the window's
first request, the weights made on the device, the weight bank built, the
cell's programs compiled (JAX's persistent cache lives in
``<checkout>/.jax_cache`` unless ``JAX_COMPILATION_CACHE_DIR`` says
otherwise). It exits non-zero, printing no result, without a TPU or with
fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def fail(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr)
    sys.exit(1)


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def prepare(workload: str):
    """(harness module, cell) with the program importable, JAX's compile
    cache in place and the chip looked for; exits without one."""
    if not (ROOT / "src" / "repro").is_dir():
        fail(f"the program is not in this checkout ({ROOT / 'src'})")
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]

    import jax
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    import harness
    cell = harness.load_cell(workload)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"no TPU: JAX's first device is {devs[0].platform!r} "
             f"({devs[0].device_kind})")
    if len(devs) < cell.workload["chips"]:
        fail(f"{len(devs)} chips, the cell asks for {cell.workload['chips']}")
    return harness, cell


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    harness, cell = prepare(args.workload)
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                         T_START, log=log)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
