"""The guard: any later tree is held to the record of what the benchmark's
last `benchmark` PR was accepted with (`fixtures/accepted.json`, written by
`_accepted.py --write`, never by hand). One case a rule, on the tree as
committed; then a rehearsal on a COPY of the real tree of what the next
`model_config` PR does (a configuration of another architecture, its cell
and its per-layer metric appended: every rule still passes and the harness
finds the new cell), and of three things no later PR may do, each refused
by the rule meant for it."""

import json
import os

import pytest

from . import _accepted, _paths
from .test_bench_admission import copy_the_benchmark, in_tree, lay_another_architecture_over

STANDS_FIRST = "accepted_{}_stand_first_in_place_as_they_were".format
BYTE_FOR_BYTE = "every_accepted_file_is_there_byte_for_byte"


@pytest.mark.parametrize("rule", sorted(_accepted.RULES))
def test_the_tree_keeps_what_was_accepted(rule):
    accepted = _accepted.load(_paths.REPO_ROOT, _accepted.RECORD)
    assert _accepted.RULES[rule](_paths.REPO_ROOT, accepted) == []


def test_the_record_holds_what_its_function_takes():
    """Written by `_accepted.py --write` and not by hand: what the function
    takes from the tree today is the record, but for `about`, until a PR
    appends (and then the guard above says what it may append)."""
    accepted = _accepted.load(_paths.REPO_ROOT, _accepted.RECORD)
    assert set(accepted) == {"about", "files", "benchmark"} and accepted["about"]
    assert _accepted.RECORD not in accepted["files"]
    assert all(rel.startswith(_accepted.HELD) and "__pycache__" not in rel for rel in accepted["files"])
    assert set(accepted["files"]) <= set(_accepted.held_files(_paths.REPO_ROOT))


@pytest.fixture(scope="module")
def appended(tmp_path_factory):
    """A copy of the real tree, the record with it, after the next
    `model_config` PR: `fixtures/other_arch`'s files and entries."""
    root = str(tmp_path_factory.mktemp("appended"))
    entries = lay_another_architecture_over(root, copy_the_benchmark(root, with_its_tests=True))
    was, now = _accepted.load(root, _accepted.RECORD)["benchmark"], _accepted.load(root, "BENCHMARK.json")
    for which, new in entries.items():  # the rehearsal does append: behind what was accepted
        assert now[which] == was[which] + new and new
    return {"root": root, "entries": entries}


@pytest.mark.parametrize("rule", sorted(_accepted.RULES))
def test_a_configuration_a_cell_and_a_metric_appended_break_no_rule(appended, rule):
    root = appended["root"]
    accepted = _accepted.load(root, _accepted.RECORD)
    assert _accepted.RULES[rule](root, accepted) == []


def test_the_harness_finds_the_appended_cell(appended):
    new = appended["entries"]
    cell = in_tree(appended["root"], "load", new["workloads"][0]["name"])
    assert "run_failed" not in cell and new["per_layer"][0]["name"] in cell["per_layer"]
    assert cell["reference"].startswith(appended["root"]) and cell["counts_file"].startswith(appended["root"])


def edit_an_accepted_entry(root: str) -> None:
    bench = _accepted.load(root, "BENCHMARK.json")
    bench["workloads"][0]["why"] += " (re-worded)"
    write_json(os.path.join(root, "BENCHMARK.json"), bench)


def insert_before_an_accepted_entry(root: str) -> None:
    bench = _accepted.load(root, "BENCHMARK.json")
    bench["configs"].insert(0, bench["configs"].pop())
    write_json(os.path.join(root, "BENCHMARK.json"), bench)


def change_a_byte_of_an_accepted_file(root: str) -> None:
    with open(os.path.join(root, "benchmark", "benchlib", "emit.py"), "a") as f:
        f.write("\n")


def write_json(path: str, data) -> None:
    with open(path, "w") as f:
        json.dump(data, f, indent=2)


@pytest.mark.parametrize("harm, refused_by", [
    (edit_an_accepted_entry, STANDS_FIRST("workloads")),
    (insert_before_an_accepted_entry, STANDS_FIRST("configs")),
    (change_a_byte_of_an_accepted_file, BYTE_FOR_BYTE),
], ids=["entry_edited", "entry_inserted_before", "byte_changed"])
def test_what_no_later_pr_may_do_is_refused_by_the_rule_meant_for_it(tmp_path, harm, refused_by):
    root = str(tmp_path / "root")
    os.mkdir(root)
    lay_another_architecture_over(root, copy_the_benchmark(root, with_its_tests=True))
    assert _accepted.broken(root) == {}
    harm(root)
    found = _accepted.broken(root)
    assert sorted(found) == [refused_by], found
