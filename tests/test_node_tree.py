"""The scheduler cache's node tree (``ksim_tpu/scheduler/nodetree.py``;
upstream ``pkg/scheduler/internal/cache/node_tree.go``): the order a sampling
attempt walks the nodes in.  Every expectation is written out by hand from the
contract in docs/jobs.md."""

from __future__ import annotations

import numpy as np
import pytest

from ksim_tpu.scheduler.nodetree import NodeTree, zone_key

ZONE, REGION = "topology.kubernetes.io/zone", "topology.kubernetes.io/region"
OLD_ZONE, OLD_REGION = ("failure-domain.beta.kubernetes.io/zone",
                        "failure-domain.beta.kubernetes.io/region")


def node(name: str, **labels) -> dict:
    return {"metadata": {"name": name, "labels": dict(labels)}}


def zoned(name: str, zone: str) -> dict:
    return node(name, **{ZONE: zone})


def tree_of(*nodes) -> NodeTree:
    tree = NodeTree()
    tree.sync(list(nodes))
    return tree


@pytest.mark.parametrize("labels, want", [
    ({}, ""),
    ({ZONE: "z1"}, ":\x00:z1"),
    ({REGION: "r1"}, "r1:\x00:"),
    ({REGION: "r1", ZONE: "z1"}, "r1:\x00:z1"),
    ({OLD_ZONE: "z1", OLD_REGION: "r1"}, "r1:\x00:z1"),
    ({ZONE: "new", OLD_ZONE: "old"}, ":\x00:new"),
    ({OLD_ZONE: "z1", REGION: "r1"}, "r1:\x00:z1"),
    ({"disk": "ssd"}, ""),
], ids=["none", "zone", "region", "both", "legacy", "ga-first", "mixed", "other-label"])
def test_zone_key(labels, want):
    assert zone_key(node("n", **labels)) == want
    assert zone_key({"metadata": {"name": "n"}}) == ""


def test_uneven_zones_are_dealt_round_robin_until_all_are_out():
    """a: 4 nodes, b: 1, c: 2.  Zones in order of first appearance BY NAME
    (n0 is in b), each zone's nodes in name order."""
    tree = tree_of(zoned("n0", "b"), zoned("n1", "a"), zoned("n2", "a"), zoned("n3", "c"),
                   zoned("n4", "a"), zoned("n5", "c"), zoned("n6", "a"))
    assert [z.split("\x00:")[1] for z in tree.zones] == ["b", "a", "c"]
    assert tree.list() == ["n0", "n1", "n3", "n2", "n5", "n4", "n6"]
    assert len(tree) == 7


def test_nodes_that_join_together_join_by_name_whatever_order_they_come_in():
    a = tree_of(zoned("n2", "a"), zoned("n0", "b"), zoned("n1", "a"))
    b = tree_of(zoned("n0", "b"), zoned("n1", "a"), zoned("n2", "a"))
    assert a.list() == b.list() == ["n0", "n1", "n2"]
    assert a.zones == b.zones


def test_a_zone_that_empties_goes_and_comes_back_last():
    tree = tree_of(zoned("n0", "a"), zoned("n1", "b"), zoned("n2", "c"), zoned("n3", "a"))
    assert tree.list() == ["n0", "n1", "n2", "n3"]
    tree.sync([zoned("n0", "a"), zoned("n2", "c"), zoned("n3", "a")])   # b's only node goes
    assert len(tree.zones) == 2 and tree.list() == ["n0", "n2", "n3"]
    tree.sync([zoned("n0", "a"), zoned("n2", "c"), zoned("n3", "a"), zoned("n1", "b")])
    assert [z[-1] for z in tree.zones] == ["a", "c", "b"]
    assert tree.list() == ["n0", "n2", "n1", "n3"]


def test_a_removal_keeps_the_others_in_their_order_and_a_new_node_joins_its_zone_last():
    """Not the featurizer's swap-remove: nobody takes the place of a node
    that went."""
    tree = tree_of(*(node(f"n{i}") for i in range(5)))
    tree.sync([node(f"n{i}") for i in (0, 2, 3, 4)])
    assert tree.list() == ["n0", "n2", "n3", "n4"]
    tree.sync([node(f"n{i}") for i in (0, 2, 3, 4)] + [node("n1")])
    assert tree.list() == ["n0", "n2", "n3", "n4", "n1"]


def test_a_relabelled_node_is_removed_and_added():
    tree = tree_of(zoned("n0", "a"), zoned("n1", "a"), zoned("n2", "b"), zoned("n3", "b"))
    assert tree.list() == ["n0", "n2", "n1", "n3"]
    tree.sync([zoned("n0", "b"), zoned("n1", "a"), zoned("n2", "b"), zoned("n3", "b")])
    assert tree.tree[tree.zones[0]] == ["n1"] and tree.tree[tree.zones[1]] == ["n2", "n3", "n0"]
    assert tree.list() == ["n1", "n2", "n3", "n0"]
    # The same labels again: nothing moves.
    tree.sync([zoned("n3", "b"), zoned("n2", "b"), zoned("n1", "a"), zoned("n0", "b")])
    assert tree.list() == ["n1", "n2", "n3", "n0"]


def test_region_and_zone_make_the_key():
    tree = tree_of(node("n0", **{REGION: "r1", ZONE: "z"}), node("n1", **{REGION: "r2", ZONE: "z"}),
                   node("n2", **{REGION: "r1", ZONE: "z"}), node("n3", **{OLD_ZONE: "z", OLD_REGION: "r2"}))
    assert tree.zones == ["r1:\x00:z", "r2:\x00:z"]
    assert tree.list() == ["n0", "n1", "n2", "n3"]
    assert tree.tree["r2:\x00:z"] == ["n1", "n3"]


def test_unlabelled_nodes_are_one_zone_in_the_order_they_joined():
    names = [f"n{i:03d}" for i in range(40)]
    tree = tree_of(*(node(n) for n in reversed(names)))
    assert tree.zones == [""] and tree.list() == names
    slot_of = {n: i for i, n in enumerate(names)}
    assert (tree.positions(slot_of, 48, 99)[:40] == np.arange(40)).all()
    assert (tree.positions(slot_of, 48, 99)[40:] == 99).all()


def test_positions_give_every_slot_its_place_in_the_list():
    tree = tree_of(zoned("n0", "a"), zoned("n1", "a"), zoned("n2", "b"), zoned("n3", "a"))
    assert tree.list() == ["n0", "n2", "n1", "n3"]
    pos = tree.positions({"n0": 0, "n1": 1, "n2": 2, "n3": 3}, 6, 7)
    assert pos.tolist() == [0, 2, 1, 3, 7, 7]


def test_the_carry_restores_zones_and_order():
    tree = tree_of(zoned("n0", "b"), zoned("n1", "a"), zoned("n2", "a"), zoned("n3", "c"))
    tree.sync([zoned("n0", "b"), zoned("n2", "a"), zoned("n3", "c"), zoned("n9", "a"), zoned("n1", "c")])
    back = NodeTree.from_carry(tree.to_carry())
    assert back.zones == tree.zones and back.tree == tree.tree and back.list() == tree.list()
    assert back.zone_of == tree.zone_of
    again = tree.copy()
    again.remove("n0")
    assert "n0" in tree.zone_of and again.list() != tree.list()
    assert NodeTree.from_carry(None).list() == [] and NodeTree().zones == []
