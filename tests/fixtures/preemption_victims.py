"""Hand-derived preemption victim-search fixtures.

Each case is worked out BY HAND from the upstream algorithm definitions
(selectVictimsOnNode's remove-all / reprieve-in-MoreImportantPod-order
loop, pickOneNodeForPreemption's narrowing criteria, and
PodEligibleToPreemptOthers) — never by running this repo's oracle or
kernels (repo CLAUDE.md: fixtures are the independent side of parity).
The arithmetic is single-resource CPU so every fit check is checkable in
one's head; the derivation for each case is in its comment.

Consumed by tests/test_preemption_fixtures.py (host oracle path) and
tests/test_replay_device.py (on-device victim search) — both must land
on the same nominated node and the same victims IN THE SAME ORDER
(victims are appended in reprieve = MoreImportantPod order: higher
priority first, then earlier start time).
"""

from __future__ import annotations

# Node: (name, cpu).  Victim: (name, node, cpu, priority, start_time or
# None -> no status.startTime, creationTimestamp is the fallback).
# Preemptor: (cpu, priority, preemptionPolicy or None).
# expected_nominated: node name or None.
# expected_victims: names in eviction (reprieve) order.
CASES = [
    {
        # Node full: 4 x 1cpu victims prio 1..4; preemptor needs 2.
        # Remove all -> 4 free, fits.  Reprieve most-important first:
        # +prio4 (3 free >= 2, stays), +prio3 (2 free >= 2, stays),
        # +prio2 (1 free < 2, VICTIM), +prio1 (1 free < 2, VICTIM).
        "name": "reprieve_minimal_set",
        "nodes": [("n0", "4")],
        "victims": [
            ("v1", "n0", "1", 1, None),
            ("v2", "n0", "1", 2, None),
            ("v3", "n0", "1", 3, None),
            ("v4", "n0", "1", 4, None),
        ],
        "preemptor": ("2", 10, None),
        "expected_nominated": "n0",
        "expected_victims": ["v2", "v1"],
    },
    {
        # Equal priorities: MoreImportantPod falls to start time, the
        # EARLIER-started pod is more important.  Node cpu 3, three
        # 1cpu victims prio 5 started Jan/Feb/Mar; preemptor needs 1.
        # Reprieve order Jan, Feb, Mar: +Jan (2 free), +Feb (1 free),
        # +Mar (0 free < 1, VICTIM).
        "name": "start_time_reprieve_order",
        "nodes": [("n0", "3")],
        "victims": [
            ("mar", "n0", "1", 5, "2026-03-01T00:00:00Z"),
            ("jan", "n0", "1", 5, "2026-01-01T00:00:00Z"),
            ("feb", "n0", "1", 5, "2026-02-01T00:00:00Z"),
        ],
        "preemptor": ("1", 10, None),
        "expected_nominated": "n0",
        "expected_victims": ["mar"],
    },
    {
        # preemptionPolicy=Never opts the preemptor out entirely, even
        # with an otherwise-perfect candidate available.
        "name": "preemption_policy_never",
        "nodes": [("n0", "1")],
        "victims": [("low", "n0", "1", 1, None)],
        "preemptor": ("1", 10, "Never"),
        "expected_nominated": None,
        "expected_victims": [],
    },
    {
        # pickOneNode criterion 1: lowest highest-victim priority.
        # Both nodes need their single victim evicted; a's victim has
        # priority 2 < b's 8.
        "name": "pick_lowest_top_priority",
        "nodes": [("a", "1"), ("b", "1")],
        "victims": [
            ("va", "a", "1", 2, None),
            ("vb", "b", "1", 8, None),
        ],
        "preemptor": ("1", 10, None),
        "expected_nominated": "a",
        "expected_victims": ["va"],
    },
    {
        # Criterion 2: highest priorities tie (3 == 3), priority sums
        # decide: a = 3+1 = 4 < b = 3+2 = 5.  Preemptor needs the whole
        # node (cpu 2 of 2), so both victims fall on each node.
        "name": "pick_smallest_priority_sum",
        "nodes": [("a", "2"), ("b", "2")],
        "victims": [
            ("a-hi", "a", "1", 3, None),
            ("a-lo", "a", "1", 1, None),
            ("b-hi", "b", "1", 3, None),
            ("b-lo", "b", "1", 2, None),
        ],
        "preemptor": ("2", 10, None),
        "expected_nominated": "a",
        "expected_victims": ["a-hi", "a-lo"],
    },
    {
        # Criterion 4: priorities, sums and counts all tie; the node
        # whose highest-priority victim started LATEST (did the least
        # work) wins -> b (June > January).
        "name": "pick_latest_top_priority_start",
        "nodes": [("a", "1"), ("b", "1")],
        "victims": [
            ("va", "a", "1", 5, "2026-01-01T00:00:00Z"),
            ("vb", "b", "1", 5, "2026-06-01T00:00:00Z"),
        ],
        "preemptor": ("1", 10, None),
        "expected_nominated": "b",
        "expected_victims": ["vb"],
    },
    {
        # startTime fallback: no status.startTime anywhere, so the
        # comparison runs on creationTimestamps (set per victim by the
        # harness from `created`); b's victim was created later ->
        # latest earliest-top-start -> b.
        "name": "start_time_falls_back_to_creation",
        "nodes": [("a", "1"), ("b", "1")],
        "victims": [
            ("va", "a", "1", 5, None, "2026-01-01T00:00:00Z"),
            ("vb", "b", "1", 5, None, "2026-02-01T00:00:00Z"),
        ],
        "preemptor": ("1", 10, None),
        "expected_nominated": "b",
        "expected_victims": ["vb"],
    },
    {
        # Criterion 2 as upstream writes it: every victim's priority
        # enters the sum with MaxInt32+1 added, so that fewer victims
        # weigh less.  The preemptor needs the whole node (cpu 2 of 2),
        # so every pod falls on both.  Highest priorities tie (3 == 3).
        # a: 3 + 1 + 2 x 2**31; b: 3 + 0 + 0 + 3 x 2**31 -> a.  The bare
        # sums (4 against 3) would have chosen b (PR 32).
        "name": "priority_sum_counts_each_victim",
        "nodes": [("a", "2"), ("b", "2")],
        "victims": [
            ("a-hi", "a", "1", 3, None),
            ("a-lo", "a", "1", 1, None),
            ("b-hi", "b", "1", 3, None),
            ("b-m", "b", "500m", 0, None),
            ("b-n", "b", "500m", 0, None),
        ],
        "preemptor": ("2", 10, None),
        "expected_nominated": "a",
        "expected_victims": ["a-hi", "a-lo"],
    },
]
