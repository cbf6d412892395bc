"""Run one benchmark cell once, on the chips of the machine it starts on.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. The cell, its configuration and its traffic
mix are found by name through ``BENCHMARK.json``. The run loads, warms up,
measures for ``--seconds``, checks every served task against the plain
reference, and prints as the last line of standard output one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer metrics), ``device`` and, with
``--trace 1``, ``breakdown``; last of all ``checks``, each compared number
beside its limit, which also end standard error.

It exits non-zero and prints no result where JAX finds no TPU, or fewer
chips than the cell asks for. JAX's compile cache lives in ``.jax_cache``
at the checkout's root.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds < 1:
        ap.error("--seed must be >= 0 and --seconds >= 1")

    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(ROOT / "src"))
    from harness import spec

    bench = spec.load(ROOT)
    cell = spec.cell(bench, args.workload)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    kind = devices[0].platform
    if kind != "tpu":
        print(f"chipbench: needs a TPU, JAX found {kind!r}", file=sys.stderr)
        return 2
    if len(devices) < int(cell["chips"]):
        print(f"chipbench: {args.workload} needs {cell['chips']} chips, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2
    device = {"platform": kind, "kind": devices[0].device_kind,
              "count": len(devices)}
    from harness import driver

    out = driver.run(ROOT, args.workload, args.seed, args.seconds,
                     bool(args.trace), T_START, device)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
