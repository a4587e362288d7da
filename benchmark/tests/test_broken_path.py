"""A whole run of the harness (rehearsal sizes, so no chip is looked for)
with the timed path broken underneath: an answer altered where the client
receives it.  ``correct`` has to come out false — and true when nothing is
broken.  A run that cannot succeed (a job that leaves the device path, a
result without a key the guarantees name) ends at once, non-zero, with no
result line."""

import json
import re
import time

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


def test_a_job_that_falls_back_ends_the_run_at_once(monkeypatch, capsys):
    """A stream the lowering refuses (a pod with a host port in step 3): the
    job's event stream says ``replay.fallback`` with the reason, the client
    cancels the job and the run ends non-zero within seconds, before any
    warm-up has finished, with the reason on stderr and nothing on stdout."""
    real = harness.build_inputs

    def refused(config, traffic, seed):
        inputs = real(config, traffic, seed)
        pod = next(op for op in inputs["operations"] if op["step"] == 3
                   and op.get("createOperation", {}).get("object", {}).get("kind") == "Pod")
        pod["createOperation"]["object"]["spec"]["containers"][0]["ports"] = [{"hostPort": 8080}]
        body = json.loads(inputs["body"])
        body["spec"]["scenario"]["operations"] = inputs["operations"]
        return dict(inputs, body=json.dumps(body).encode())

    monkeypatch.setattr(harness, "build_inputs", refused)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    started = time.monotonic()
    rc = harness.main(["--workload", "churn-2k_prefix6k", "--seed", "3", "--seconds", "1",
                       "--trace", "0", "--rehearsal"])
    took = time.monotonic() - started
    out = capsys.readouterr()
    assert rc != 0 and out.out == ""
    assert "CannotSucceed" in out.err and "replay.fallback" in out.err and "host_ports" in out.err
    after = float(re.search(r"emitted replay.fallback after ([0-9.]+) s", out.err).group(1))
    assert after < 30 and took < 60   # the server's start and its first compile, not a request cap


def test_a_result_without_a_guaranteed_key_ends_the_run_at_the_first_warm_up(monkeypatch, capsys):
    """A program that does not give a guarantee the configuration names (a
    parent that lacks a new one): the first warm-up's document shows it, and
    the run ends there instead of measuring what cannot be correct."""
    real = client.Server.run_job
    calls = []

    def altered(self, body, deadline):
        rec = real(self, body, deadline)
        calls.append(1)
        del rec["doc"]["replay"]["unsupported"]
        return rec

    monkeypatch.setattr(client.Server, "run_job", altered)
    rc, _, lines = None, None, None
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    rc = harness.main(["--workload", "churn-2k_prefix6k", "--seed", "0", "--seconds", "1",
                       "--trace", "0", "--rehearsal"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == "" and len(calls) == 1
    assert "CannotSucceed" in out.err and "replay.unsupported" in out.err
