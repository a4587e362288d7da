"""The control of ``correct``, at a size a test run can hold: the rehearsal
server (CPU, 100 pods x 20 nodes / a 200-node churn prefix) through
``tools/seeds.py --control``.

- import deployment: the program's exports agree with the plain reference;
  the same reference at bfloat16, put in the program's place, does not.
- job deployment: the job's counts equal the plain sequential replay's; the
  counts of the same replay at bfloat16, put in the job's result document,
  do not (``test_replay.py`` shows that at the cells' own sizes).

On the chip, at the cells' own sizes, the same tool read the numbers the
limits were set from (PERF.md section 2)."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def seeds(workload, seed_list):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "tools", "seeds.py"), "--workload", workload,
         "--seeds", seed_list, "--seconds", "1", "--control", "--rehearsal"],
        cwd=ROOT, env=env, stdout=subprocess.PIPE, timeout=600, check=False)
    docs = [json.loads(line) for line in out.stdout.decode().splitlines() if line.startswith("{")]
    return out.returncode, docs


@pytest.mark.parametrize("workload,seed_list", [
    ("import-1k_full", "7,4000000007"),
    ("churn-2k_prefix6k", "0,11"),
    ("churn-2k_stream", "3,2147483693"),
])
def test_sound_runs_are_correct_and_the_control_is_not(workload, seed_list):
    rc, docs = seeds(workload, seed_list)
    sound = [d for d in docs if "seed" in d and "control" not in d]
    control = [d for d in docs if "control" in d]
    assert rc == 0 and len(sound) == 2 and len(control) >= 2
    assert all(d["correct"] for d in sound), sound
    assert not any(d["correct"] for d in control), control
    for d in control:
        if "reference" in d:  # far above the limit, not just over it
            assert d["reference"]["reference.score_mismatch_share"] > 0.05
        else:  # the one comparison the lower precision has to fail
            assert "job.counts_vs_reference_replay" in {x["name"] for x in d["failing"]}


def test_another_cluster_is_judged_by_the_reference_not_a_lock():
    """Objects drawn from other base seeds have no lock; the replay judges."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "tools", "seeds.py"), "--workload",
         "churn-2k_prefix6k", "--seeds", "4", "--base-seeds", "1,2", "--seconds", "1", "--rehearsal"],
        cwd=ROOT, env=env, stdout=subprocess.PIPE, timeout=600, check=False)
    docs = [json.loads(ln) for ln in out.stdout.decode().splitlines() if ln.startswith('{"base_seed"')]
    assert out.returncode == 0 and [d["base_seed"] for d in docs] == [1, 2]
    assert all(d["correct"] for d in docs), docs
    assert docs[0]["job_counts"] != docs[1]["job_counts"]
