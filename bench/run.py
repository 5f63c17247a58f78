"""Run one benchmark cell once on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints notes and each compared number beside its limit on stderr, and one
JSON object as the last line of stdout: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1`` a
``breakdown``, and last ``checks``.  Exits non-zero and prints no result
when JAX finds no TPU, or fewer chips than the cell asks for, or when the
program under test (``src/repro``) is not beside the benchmark.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402


def process_age() -> float:
    """Seconds since this process started, read at ``T_START``."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - start_ticks / os.sysconf("SC_CLK_TCK")
        return max(0.0, age - (time.perf_counter() - T_START))
    except (OSError, ValueError, IndexError):
        return 0.0


def main(argv=None) -> int:
    t0 = T_START - process_age()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"bench: the program under test is not at {src}/repro",
              file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    sys.path.insert(0, root)

    from bench import harness

    bench = harness.load_benchmark(root)
    cell = harness.cell_spec(args.workload, bench, root)

    import jax

    # the persistent compile cache lives at a fixed path in the checkout;
    # the program's own cache helper takes the same directory
    os.environ["JAX_COMPILATION_CACHE_DIR"] = harness.CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", harness.CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: needs a TPU, JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < cell["chips"]:
        print(f"bench: {args.workload} needs {cell['chips']} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 1

    result, out = harness.run_cell(
        args.workload, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), devices=devices[: cell["chips"]], t0=t0,
        bench=bench, root=root)
    harness.emit(result, out["checks"], out.get("notes"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
