"""The per-layer metrics that read the program's phase spans, timers and
runtime counters (PR 25): every metric file loads, names a reader kind
``readers.py`` has, and resolves against canned documents — and against a
program that has no such span or counter it reads nothing and does not
raise (the parent commit of the PR that adds them)."""

import json
import os

import readers

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

LOWER_TOTAL = ["replay.lower", "replay.prelower", "replay.lower.parse", "replay.lower.warm",
               "replay.lower.universe", "replay.lower.featurize", "replay.lower.tensors"]


def spec(name):
    with open(os.path.join(ROOT, "benchmark", "metrics", f"{name}.json"), encoding="utf-8") as f:
        return json.load(f)


def read(name, ctx):
    s = spec(name)
    return readers.KINDS[s["kind"]](ctx, s)


def job(spans, doc):
    return {"wall_s": 2.0, "units": 2000, "spans": spans, "doc": doc}


# One 2,000-event job: replay.lower 1.0 s on the main thread with three
# children (0.1 + 0.6 + 0.2, so 0.1 s its own), a prelower of 0.3 s with a
# 0.2-s parse inside, and the dispatch worker's four spans on thread 2.
SPANS = [
    (0.0, 1.0, "replay.lower", 1), (0.0, 0.1, "replay.lower.universe", 1),
    (0.1, 0.7, "replay.lower.featurize", 1), (0.7, 0.9, "replay.lower.tensors", 1),
    (1.0, 2.0, "replay.dispatch", 1), (1.1, 1.4, "replay.prelower", 1),
    (1.1, 1.3, "replay.lower.parse", 1),
    (1.0, 1.05, "replay.pack", 2), (1.05, 1.85, "replay.exec", 2),
    (1.85, 1.95, "replay.pull", 2), (1.95, 2.0, "replay.decode", 2),
]
DOC = {"replay": {"device_wait_s": 0.8},
       "runtime": {"gc_gen2_pause_s": 0.25, "xla_compiles": 0}}


def close(a, b):
    return a is not None and abs(a - b) < 1e-6


def test_job_metrics_resolve_against_a_canned_job():
    ctx = {"requests": [job(SPANS, DOC)]}
    # 1.0 s of lower + 0.3 s of prelower, children included, per 2 kevents.
    assert spec("lower_total_ms_per_kevent")["spans"] == LOWER_TOTAL
    assert close(read("lower_total_ms_per_kevent", ctx), 650.0)
    assert close(read("lower_featurize_ms_per_kevent", ctx), 300.0)
    assert close(read("pack_ms_per_kevent", ctx), 25.0)
    assert close(read("pull_ms_per_kevent", ctx), 50.0)
    assert close(read("device_wait_s_per_job", ctx), 0.8)
    assert close(read("gc_pause_s_per_job", ctx), 0.25)
    assert read("compiles_per_job", ctx) == 0
    # Self time of the parents alone is their remainder: 0.1 s of replay.lower
    # + 0.1 s of replay.prelower (what ``lower_ms_per_kevent`` read until PR 31
    # retired it; the reader keeps the arithmetic for any metric that names parents).
    parents = dict(spec("lower_total_ms_per_kevent"), spans=["replay.lower", "replay.prelower"])
    assert close(readers.KINDS[parents["kind"]](ctx, parents), 100.0)


def test_lower_total_continues_the_series_of_a_program_without_children():
    old = [s for s in SPANS if s[2] in ("replay.lower", "replay.prelower", "replay.dispatch")]
    ctx = {"requests": [job(old, {"replay": {}})]}
    assert close(read("lower_total_ms_per_kevent", ctx), 650.0)
    for name in ("lower_featurize_ms_per_kevent", "pack_ms_per_kevent", "pull_ms_per_kevent",
                 "device_wait_s_per_job", "gc_pause_s_per_job", "compiles_per_job"):
        assert read(name, ctx) is None, name


def metrics_doc(scale):
    timers = ("render", "bind_store", "engine_pack", "engine_pull", "import_load",
              "export_snap", "export_encode", "gc_gen2")
    return {"counters": {"xla_compiles": 4 * scale, "scheduling_passes": scale},
            "timings": {t: {"total_seconds": (i + 1) * 0.01 * scale, "count": scale}
                        for i, t in enumerate(timers)}}


def test_import_metrics_read_the_growth_across_a_request():
    ctx = {"requests": [{"wall_s": 4.0, "units": 1000,
                         "metrics_before": metrics_doc(1), "metrics_after": metrics_doc(2)}]}
    names = ("render_ms", "bind_store_ms", "engine_pack_ms", "engine_pull_ms", "import_load_ms",
             "export_snap_ms", "export_encode_ms", "gc_pause_ms.import")
    for i, name in enumerate(names):
        assert close(read(name, ctx), (i + 1) * 10.0), name
    assert read("compiles_per_import", ctx) == 4
    # A program without these timers and counters: nothing to read.
    bare = {"counters": {"scheduling_passes": 1}, "timings": {"bind": {"total_seconds": 1.0}}}
    ctx = {"requests": [{"wall_s": 4.0, "units": 1000,
                         "metrics_before": bare, "metrics_after": bare}]}
    for name in names + ("compiles_per_import",):
        assert read(name, ctx) is None, name
