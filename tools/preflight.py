#!/usr/bin/env python
"""Backend preflight CLI: probe an accelerator, print ONE classified
verdict, never hang.

    python tools/preflight.py                      # default backend
    python tools/preflight.py --platform cpu       # explicit platform
    python tools/preflight.py --platform tpu --json

A structured probe (``multidisttorch_tpu/utils/preflight.py``) for
operators and for tools that start worlds — this process never touches
jax, and nothing that trains calls it: bounded out-of-process
init (on failure: /proc leaked-plugin scan + one delayed retry),
device enumeration, a tiny compile+execute canary, and
``memory_stats()`` — folded to one verdict
from the closed taxonomy in docs/OBSERVABILITY.md ("Fleet" section).
Every stage has a hard timeout and the probing happens in
subprocesses, so a wedged backend yields ``wedged_*`` (diagnosed) and
an absent one yields ``backend_absent`` (fast) — this tool's exit is
ALWAYS bounded.

Exit code: 0 when the verdict is usable (``healthy`` /
``transient_recovered``), 3 otherwise. With ``--telemetry-dir`` the
probe additionally streams ``preflight_*`` events to a JSONL sink
(the same events the elastic supervisor emits when it preflights a
world — see tools/sweep_supervisor.py).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

# Allow running straight from a checkout (tools/ is not a package).
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from multidisttorch_tpu.utils import preflight  # noqa: E402


def render(report: dict) -> str:
    lines = [
        f"preflight  platform={report['platform_requested']}  "
        f"verdict={report['verdict']}  usable={report['usable']}  "
        f"({report['elapsed_s']:.1f}s)",
        f"  reason: {report['verdict_reason']}",
    ]
    for st in report["stages"]:
        ok = "ok" if st.get("ok") else "FAIL"
        extra = ""
        if st["stage"] == "plugin_scan":
            extra = (
                f" holders={st.get('holders')} "
                f"plugin_procs={st.get('plugin_processes')}"
            )
        elif st["stage"] == "enumerate":
            extra = (
                f" {st.get('n_devices')}x {st.get('device_kind')} "
                f"({st.get('platform')})"
            )
        elif st["stage"] == "canary" and st.get("ok"):
            extra = f" value={st.get('canary_value')}"
        elif st["stage"] == "compile_cache":
            extra = (
                f" verdict={st.get('cache_verdict')} "
                f"scanned={st.get('scanned')} "
                f"rejected={st.get('rejected')}"
            )
        el = st.get("elapsed_s")
        lines.append(
            f"  {st['stage']:<12} {ok:<4}"
            + (f" {el:.1f}s" if el is not None else "")
            + extra
        )
    if report.get("memory_stats"):
        ms = report["memory_stats"]
        lines.append(
            "  memory_stats: "
            + ", ".join(f"{k}={v}" for k, v in sorted(ms.items())[:4])
        )
    return "\n".join(lines)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="classified, bounded backend preflight probe "
        "(docs/OBSERVABILITY.md \"Fleet\")"
    )
    parser.add_argument(
        "--platform", default=None,
        help="probe this JAX platform (subprocess JAX_PLATFORMS); "
        "default: jax's default backend",
    )
    parser.add_argument("--json", action="store_true",
                        help="print the full report as one JSON object")
    parser.add_argument("--out", default=None,
                        help="also write the JSON report here")
    parser.add_argument("--init-timeout", type=float,
                        default=preflight.PREFLIGHT_TIMEOUT_S)
    parser.add_argument("--retry-timeout", type=float,
                        default=preflight.RETRY_TIMEOUT_S)
    parser.add_argument("--retry-delay", type=float,
                        default=preflight.RETRY_DELAY_S)
    parser.add_argument("--canary-timeout", type=float,
                        default=preflight.CANARY_TIMEOUT_S)
    parser.add_argument("--no-canary", action="store_true",
                        help="skip the compile+execute canary stage")
    parser.add_argument("--no-scan", action="store_true",
                        help="skip the /proc leaked-plugin scan")
    parser.add_argument(
        "--compile-cache", action="store_true",
        help="also probe the quarantined persistent executable cache: "
        "CRC sidecar scan + one subprocess canary protocol run "
        "(docs/COMPILE.md); the cache verdict rides the report, "
        "orthogonal to backend usability",
    )
    parser.add_argument(
        "--cache-dir", default=None,
        help="cache directory for --compile-cache "
        "(default: the shared utils/compile_cache.py resolution)",
    )
    parser.add_argument(
        "--telemetry-dir", default=None,
        help="stream preflight_* events to {dir}/events.jsonl",
    )
    args = parser.parse_args(argv)

    if args.telemetry_dir:
        from multidisttorch_tpu import telemetry

        telemetry.configure(args.telemetry_dir)
    report = preflight.run_preflight(
        args.platform,
        init_timeout_s=int(args.init_timeout),
        retry_timeout_s=int(args.retry_timeout),
        retry_delay_s=int(args.retry_delay),
        canary=not args.no_canary,
        canary_timeout_s=int(args.canary_timeout),
        scan=not args.no_scan,
        compile_cache=args.compile_cache,
        compile_cache_dir=args.cache_dir,
    )
    if args.telemetry_dir:
        from multidisttorch_tpu import telemetry

        telemetry.disable()
    if args.json:
        print(json.dumps(report, default=str))
    else:
        print(render(report))
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        tmp = args.out + ".tmp"
        with open(tmp, "w") as f:
            json.dump(report, f, indent=2, default=str)
        os.replace(tmp, args.out)
    return 0 if report["usable"] else 3


if __name__ == "__main__":
    sys.exit(main())
