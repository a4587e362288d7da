"""The traffic did not move when the generators moved behind ``kinds/`` (PR 31):
the bytes of every cell's request body, at seeds 0 and 1, at the cell's own
size and at the rehearsal size, against the sha256 taken on the parent commit
(bda2801) through ``run.build_inputs`` before the move.  A body repeats exactly
at one seed (``json.dumps`` of one object graph), so the bytes themselves are
pinned, not the operations."""

import hashlib

import pytest

import run as harness

PINS = [   # cell, rehearsal, seed, bytes, sha256 of inputs["body"] on the parent
    ("churn-2k_prefix6k", False, 0, 2130298, "0bfffb2c8806318734632c3c8043e86d1b4505c6715f618ade187f12b189de6f"),
    ("churn-2k_prefix6k", False, 1, 2130298, "eadb68b5510982faa204d5ae23dc91685322e9277e9933bc60978b11d6ecfca2"),
    ("churn-2k_prefix6k", True, 0, 270408, "9b9f9b2f95aac3e7f880fa2aa88d5b0bb01da5088d856158a4b7295cfdd5c2f2"),
    ("churn-2k_prefix6k", True, 1, 270408, "96a9da36328b57dc6b4aefef8a919c847db0c5e4aab3b41f62ea6b8df2def594"),
    ("import-1k_full", False, 0, 564140, "760c3bb59fe0d758e6e39a1a34727e5a0ce9d54bd9829ed8beeea1970073459e"),
    ("import-1k_full", False, 1, 564140, "5ef06dfef6cdb693d2d1a2e47cb04ed7bdbb80e06c1cf46330dccc4c4c6514a2"),
    ("import-1k_full", True, 0, 53592, "d3394125a4e3d3b22e8bd49cd7f797857e06167243ea9f6d852731938049f473"),
    ("import-1k_full", True, 1, 53592, "b179171cd5ad48f5c38412ad24ae2bade9f79b25ba45bb797bb204fea60d72a8"),
    ("churn-2k_stream", False, 0, 16195107, "b8909544bd6cf309bc8a88efc90c6a877ec4f91cdf3a631153fd3fb6aaa6e1ea"),
    ("churn-2k_stream", False, 1, 16195107, "e56f58c42367b32cd3fa69d72a2000a6ff0d8a55c47fd0af2a1f9545a5feb110"),
    ("churn-2k_stream", True, 0, 522495, "6205f5c4736b21ce63b9ea8003c2b65e55e32ddb278275927a055b7d78276a98"),
    ("churn-2k_stream", True, 1, 522495, "6191f7a99728fa18320d977a1bf7f4607c7cac03d7757163069847ed9f84e704"),
    ("burst-5k_onestep", False, 0, 5706902, "652dd477dd95f04e98b50f4b3e3f50d480ffb3197779b500d3556ee4b111b595"),
    ("burst-5k_onestep", False, 1, 5706902, "6e53b164ee5e74a35f489b699a9552a85cd3ffe4ba0f08bc9be1b5710a674e13"),
    ("burst-5k_onestep", True, 0, 223036, "081c943a1e06c9d6262928b81c6313e5848ab8bf1d7dddce740b3f4648cf67ef"),
    ("burst-5k_onestep", True, 1, 223036, "d08e7646b97db1df546ca50924205f487e13bc20e22d821a750ffaa6faf7bd57"),
]


@pytest.mark.parametrize("cell,rehearsal,seed,size,sha", PINS,
                         ids=[f"{p[0]}-{'rehearsal' if p[1] else 'full'}-{p[2]}" for p in PINS])
def test_the_request_body_is_the_parents_byte_for_byte(cell, rehearsal, seed, size, sha):
    c = harness.load_cell(harness.load("BENCHMARK.json"), cell, rehearsal)
    body = harness.build_inputs(c["config"], c["traffic"], seed)["body"]
    assert len(body) == size and hashlib.sha256(body).hexdigest() == sha


def test_the_four_cells_of_pr_31_are_pinned_at_both_sizes_and_seeds():
    cells = {w["name"] for w in harness.load("BENCHMARK.json")["workloads"]}
    assert {p[0] for p in PINS} <= cells and len(PINS) == 16
