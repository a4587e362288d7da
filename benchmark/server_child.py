"""The one process of a run that touches JAX: the product's own server
entry point (``ksim_tpu.cmd.simulator.start_simulator``), started through
this file so that the run can (1) refuse to measure on anything but the
chips the cell asks for, before a single request, and (2) read the device's
peak memory, which no served document carries, after the server has shut
down.  Both go into the JSON file named by ``BENCH_DEVICE_REPORT``.  With
``BENCH_PROFILE_DIR`` set it also turns the JAX profiler on at SIGUSR1 and
off at SIGUSR2 (Python tracer off: the server is host-bound Python and a
trace of every call would be the workload), then writes ``DONE`` there.

Usage: python benchmark/server_child.py <platform> <chips> [server args...]
"""

from __future__ import annotations

import json
import os
import sys
import time


def _report(doc: dict) -> None:
    path = os.environ["BENCH_DEVICE_REPORT"]
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(doc, f)
    os.replace(tmp, path)


def main(argv: list) -> int:
    want_platform, want_chips = argv[0], int(argv[1])
    sys.path.insert(0, os.getcwd())
    from ksim_tpu.cmd.simulator import start_simulator
    import jax

    devices = jax.local_devices()
    doc = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": None,
    }
    _report(doc)
    if doc["platform"] != want_platform or doc["count"] < want_chips:
        print(f"server_child: need {want_chips} x {want_platform}, JAX reports "
              f"{doc['count']} x {doc['platform']}", file=sys.stderr)
        return 3
    profile_dir = os.environ.get("BENCH_PROFILE_DIR")
    if profile_dir:
        import signal

        def profiler_on(signum, frame):
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(profile_dir, profiler_options=opts)
            # One event whose UNIX time is known: it puts the trace's clock
            # and the job rings' (``epoch_unix_s``) on one line.
            with jax.profiler.TraceAnnotation("bench.anchor"):
                anchor = time.time()
            with open(os.path.join(profile_dir, "ANCHOR.json"), "w", encoding="ascii") as f:
                json.dump({"unix_s": anchor}, f)

        def profiler_off(signum, frame):
            jax.profiler.stop_trace()
            with open(os.path.join(profile_dir, "DONE"), "w", encoding="ascii"):
                pass

        os.makedirs(profile_dir, exist_ok=True)
        signal.signal(signal.SIGUSR1, profiler_on)
        signal.signal(signal.SIGUSR2, profiler_off)
    rc = start_simulator(argv[2:])
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    doc["memory_peak_bytes"] = max(peaks) if peaks else None
    _report(doc)
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
