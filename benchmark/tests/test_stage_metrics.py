"""The seventeen per-layer metrics that read the timed stages, the node-table
count and the submit clock from a job's result document (PR 38): every
metric file loads, names a reader kind ``readers.py`` has, resolves against
a canned result document — and reads nothing, without raising, from a
document of the parent commit, which has no such keys."""

import json
import os

import pytest

import readers

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
JOB_CELLS = ["churn-2k_prefix6k", "churn-2k_stream", "burst-5k_onestep",
             "sperf-5k-preempt_basic", "sperf-5k-basic_10kpods"]

RECONCILE = {f"reconcile_{s}_s_per_job": f"replay.reconcile.{s}"
             for s in ("apply", "write", "verify", "commit", "effects", "evict")}
FEATURIZE = {f"featurize_{s}_s_per_job": f"service.featurize.{s}"
             for s in ("index", "resources", "affinity", "spread", "interpod", "extras")}
FEATURIZE["featurize_program_s_per_job"] = "replay.lower.featurize.program"
PHASES = {**RECONCILE, **FEATURIZE}
SUBMIT = {"submit_s_per_job": "total_s", "submit_parse_s_per_job": "parse_s",
          "submit_build_s_per_job": "build_s"}
NAMES = sorted([*PHASES, *SUBMIT, "featurize_node_tables_built"])


def spec(name):
    with open(os.path.join(ROOT, "benchmark", "metrics", f"{name}.json"), encoding="utf-8") as f:
        return json.load(f)


def read(name, ctx):
    s = spec(name)
    return readers.KINDS[s["kind"]](ctx, s)


def doc(scale):
    """A result document whose every stage has a number of its own."""
    phases = {span: scale * (i + 1) / 100 for i, span in enumerate(sorted(PHASES.values()))}
    phases["replay.reconcile"] = 9.0
    return {"phases": phases, "replay": {"featurize_node_builds": 8 * scale},
            "submit": {"read_s": 0.001 * scale, "parse_s": 0.1 * scale, "build_s": 0.05 * scale,
                       "enqueue_s": 0.002 * scale, "total_s": 0.153 * scale}}


# A parent-commit document: phases of the spans alone, no submit block, no count.
PARENT_DOC = {"phases": {"replay.reconcile": 0.46, "replay.lower.featurize": 0.2},
              "replay": {"featurize_copied": 10999, "device_wait_s": 0.6}}


def test_there_are_seventeen():
    assert len(NAMES) == 17


@pytest.mark.parametrize("name", NAMES)
def test_metric_file_loads_and_names_a_kind(name):
    s = spec(name)
    assert s["name"] == name and s["kind"] == "job_result" and s["kind"] in readers.KINDS
    assert set(s) == {"name", "kind", "path"}


@pytest.mark.parametrize("name", NAMES)
def test_metric_resolves_against_a_canned_document_as_a_median(name):
    ctx = {"requests": [{"doc": doc(1)}, {"doc": doc(3)}, {"doc": doc(2)}]}
    want = doc(2)
    for key in spec(name)["path"]:
        want = want[key]
    assert read(name, ctx) == pytest.approx(want)


@pytest.mark.parametrize("name", NAMES)
def test_metric_reads_nothing_from_a_parent_document(name):
    assert read(name, {"requests": [{"doc": PARENT_DOC}, {"doc": None}, {}]}) is None


def test_paths_are_the_documented_ones():
    for name, span in PHASES.items():
        assert spec(name)["path"] == ["phases", span]
    for name, key in SUBMIT.items():
        assert spec(name)["path"] == ["submit", key]
    assert spec("featurize_node_tables_built")["path"] == ["replay", "featurize_node_builds"]


def test_benchmark_json_lists_them_in_one_block_and_in_the_job_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        per_layer = json.load(f)["per_layer"]
    # PR 38's seventeen closed the list at 62; later PRs append after them.
    assert len(per_layer) >= 62
    added = per_layer[45:62]
    assert sorted(m["name"] for m in added) == NAMES
    for m in added:
        assert m["moves"] == "events_per_s" and m["better"] == "lower"
        # The cells of PR 38; a later PR's cell is appended behind them.
        cells = ["sperf-5k-preempt_basic"] if m["name"] == "reconcile_evict_s_per_job" else JOB_CELLS
        assert m["workloads"][:len(cells)] == cells, m["name"]
        counted = m["name"] == "featurize_node_tables_built"
        assert (m["unit"], m["source"]) == (("count", "program_counter") if counted
                                            else ("s", "program_span"))
    # The three parents' metrics are what they were.
    for name, span in (("reconcile_ms_per_kevent", "replay.reconcile"),
                       ("lower_featurize_ms_per_kevent", "replay.lower.featurize")):
        assert spec(name) == {"name": name, "kind": "job_span_self", "spans": [span],
                              "per": "kunit", "scale": 1000.0}
