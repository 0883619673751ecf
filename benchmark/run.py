#!/usr/bin/env python3
"""One run of one benchmark cell, on the chip this process is started on.

    python3 benchmark/run.py --workload lm-dense --seed 1 --seconds 30 --trace 0

Prints progress lines, then as the last line of standard output one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device`` and, traced, ``breakdown``. Without a TPU, or
with fewer chips than the cell asks for, it exits non-zero and prints no
result: the measuring path has no CPU fallback.
"""

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_DIR = os.path.join(ROOT, ".benchmark_trace")  # every traced run replaces it


def say(msg: str) -> None:
    print(f"[benchmark] {msg}", flush=True)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args()

    if not os.path.isdir(os.path.join(ROOT, "multidisttorch_tpu")):
        print("benchmark/run.py: the program under test (multidisttorch_tpu/) "
              "is not in this checkout", file=sys.stderr)
        return 2
    sys.path.insert(0, ROOT)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # libtpu otherwise logs under /tmp

    from benchmark import cells

    cell = cells.load_cell(args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"benchmark/run.py: {args.workload} needs {cell.chips} TPU chip(s); jax found "
              f"{len(devices)} {devices[0].platform} device(s)", file=sys.stderr)
        return 2
    devices = devices[: cell.chips]

    from benchmark.compile_book import CompileBook
    from multidisttorch_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()  # $JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache
    book = CompileBook()
    entry = cell.entry()
    trace_dir = None
    if args.trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        trace_dir = TRACE_DIR
    say(f"{cell.name}: config {cell.config['name']} traffic {cell.traffic['name']} "
        f"seed {args.seed} seconds {args.seconds:g} trace {args.trace} "
        f"device_kind {devices[0].device_kind!r} chips {len(devices)} cache {cache_dir}")

    t_entry = time.perf_counter()
    record = entry.run(cell, devices, args.seed, args.seconds, trace_dir, book)
    record["t_process_start"] = T_PROCESS_START
    record["t_entry"] = t_entry
    record["device"] = {"kind": devices[0].device_kind, "count": len(devices)}

    report(record)
    if args.trace:
        metrics = cells.read_metrics(cell.per_layer, "layer_metrics", record)
    else:
        metrics = cells.read_metrics(cell.end_to_end, "end_to_end", record)
    line = {
        "correct": record["correct"],
        "attempted": record["attempted"],
        "failed": record["failed"],
        "metrics": metrics,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": record["peak_bytes"],
        },
    }
    if record["trace"] is not None:
        line["device"]["busy_s"] = record["trace"]["busy_s"]
        line["device"]["window_s"] = record["trace"]["window_s"]
        line["breakdown"] = {
            "device_ops": record["trace"]["device_ops"],
            "idle_gaps": record["trace"]["idle_gaps"],
        }
    print(json.dumps(line), flush=True)
    return 0


def report(record: dict) -> None:
    """What the last line leaves out, for whoever reads the run's output."""
    from benchmark import readings

    summary = readings.summarize(record["stamps"], record["min_readings"])
    say("spans " + " ".join(f"{k}={v:.2f}" for k, v in record["spans"].items())
        + f" startup_s={record['t_entry'] - record['t_process_start']:.2f}")
    say(f"set-up compile: hits {record['compile_setup']['hits']} misses "
        f"{record['compile_setup']['misses']} seconds {record['compile_setup']['compile_s']:.2f}")
    for note in record["reference"]["notes"]:
        say(f"reference: {note}")
    say(f"readings n={summary['n']} median_s={summary['median_s']:.6f} "
        f"max_s={summary['max_s']:.6f} whole_window_s={summary['whole_window_s']:.6f} "
        f"stall_share={summary['stall_share']:.5f}")
    say(f"losses first/last by trial {record['losses_first_last']}; checks {record['checks']}")
    say(f"peak bytes after the window {record['peak_bytes']}, at the end of the run "
        f"(reference check included) {record['peak_bytes_at_end']}")


if __name__ == "__main__":
    sys.exit(main())
