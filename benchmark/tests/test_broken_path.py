"""A whole run of the harness (rehearsal sizes, so no chip is looked for)
with the timed path broken underneath: an answer altered where the client
receives it.  ``correct`` has to come out false — and true when nothing is
broken."""

import json
import re

import client
import run as harness


def last_line(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1]), out


def drive(monkeypatch, capsys, workload, seed):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    rc = harness.main(["--workload", workload, "--seed", str(seed), "--seconds", "1",
                       "--trace", "0", "--rehearsal"])
    doc, lines = last_line(capsys)
    return rc, doc, lines


def test_sound_import_run_is_correct(monkeypatch, capsys):
    rc, doc, _ = drive(monkeypatch, capsys, "import-1k_full", 21)
    assert rc == 0 and doc["correct"] is True and doc["rehearsal"] is True
    assert "metrics" not in doc  # nothing that could read as a chip run


def test_altered_scores_in_the_export_are_caught(monkeypatch, capsys):
    real = client.Server.run_import
    # Annotation values are JSON inside JSON: a live score reads
    # \"NodeResourcesFit\":\"49\" in the raw export (its copies in
    # result-history carry three backslashes and do not match).
    live = re.compile(rb'(NodeResourcesFit\\":\\")(\d+)')

    def altered(self, body, n_pods, deadline):
        rec = real(self, body, n_pods, deadline)
        # every NodeResourcesFit score one point up
        rec["raw"] = live.sub(lambda m: m.group(1) + str(int(m.group(2)) + 1).encode(), rec["raw"])
        return rec

    monkeypatch.setattr(client.Server, "run_import", altered)
    rc, doc, lines = drive(monkeypatch, capsys, "import-1k_full", 21)
    assert rc == 1 and doc["correct"] is False
    assert any('"reference.score_mismatch_share"' in ln and '"ok": false' in ln for ln in lines)


def test_a_pod_moved_off_its_node_is_caught(monkeypatch, capsys):
    real = client.Server.run_import

    def altered(self, body, n_pods, deadline):
        rec = real(self, body, n_pods, deadline)
        doc = json.loads(rec["raw"])
        bound = [p for p in doc["pods"] if p["spec"].get("nodeName")]
        bound[0]["spec"]["nodeName"] = bound[-1]["spec"]["nodeName"]
        rec["raw"] = json.dumps(doc).encode()
        return rec

    monkeypatch.setattr(client.Server, "run_import", altered)
    rc, doc, lines = drive(monkeypatch, capsys, "import-1k_full", 22)
    assert rc == 1 and doc["correct"] is False
    assert any("export.bound_not_on_selected_node" in ln and '"ok": false' in ln for ln in lines)


def test_a_job_that_left_the_device_path_is_caught(monkeypatch, capsys):
    real = client.Server.run_job

    def altered(self, body, deadline):
        rec = real(self, body, deadline)
        rec["doc"]["replay"]["device_steps"] -= 1
        rec["doc"]["replay"]["fallback_steps"] = 1
        return rec

    monkeypatch.setattr(client.Server, "run_job", altered)
    rc, doc, _ = drive(monkeypatch, capsys, "churn-2k_prefix6k", 0)
    assert rc == 1 and doc["correct"] is False


def test_a_sound_job_run_is_correct(monkeypatch, capsys):
    rc, doc, lines = drive(monkeypatch, capsys, "churn-2k_stream", 17)
    assert rc == 0 and doc["correct"] is True
    assert any("job.counts_vs_reference_replay" in ln and '"ok": true' in ln for ln in lines)


def test_a_job_with_other_counts_than_the_replay_is_caught(monkeypatch, capsys):
    """The stream cell has no lock at rehearsal size: the plain replay alone
    has to see one pod more, in every job of the run alike."""
    real = client.Server.run_job

    def altered(self, body, deadline):
        rec = real(self, body, deadline)
        rec["doc"]["result"]["podsScheduled"] += 1
        return rec

    monkeypatch.setattr(client.Server, "run_job", altered)
    rc, doc, lines = drive(monkeypatch, capsys, "churn-2k_stream", 17)
    assert rc == 1 and doc["correct"] is False
    failed = [json.loads(ln)["compared"] for ln in lines if '"ok": false' in ln]
    assert failed == ["job.counts_vs_reference_replay"]
