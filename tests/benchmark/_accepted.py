"""What the benchmark held when its last `benchmark` PR was accepted, and
the rules that hold ANY later tree to it.

`fixtures/accepted.json` is the record: `about` (the PR and commit it was
taken at), `files` (sha256 of every file under `benchmark/` and
`tests/benchmark/` but the record itself) and `benchmark` (the whole of
`BENCHMARK.json`). It is written by this file and never by hand:

    python3 tests/benchmark/_accepted.py --write "PR <n>, on <parent commit>"

Only a `benchmark` PR runs that, as its last act. A PR of any other kind
appends behind what is there and touches no file of the record, and
`RULES` says so one rule at a time (tests/benchmark/test_bench_accepted.py
runs each as a case of its own). No rule names a configuration, a cell or a
metric, and none counts the entries: they bind the tree that comes after
the next `model_config` PR as they bind this one.

Every rule is `rule(root, accepted) -> [what is wrong, ...]`; `root` holds
`BENCHMARK.json`, `benchmark/` and `tests/benchmark/`.
"""

from __future__ import annotations

import hashlib
import json
import os
import sys

HELD = ("benchmark", "tests/benchmark")
RECORD = "tests/benchmark/fixtures/accepted.json"
LISTS = ("configs", "workloads", "end_to_end", "per_layer")
MAX_CELLS, MAX_SOURCE = 24, 200
METRICS_DIR = "benchmark/layer_metrics"
FILE_ONLY = ("reader", "args", "description")  # what a metric's file says beside its entry


def load(*path):
    with open(os.path.join(*path)) as f:
        return json.load(f)


def digest(path: str) -> str:
    with open(path, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def held_files(root: str) -> dict:
    """sha256 by path (relative to `root`, with `/`) of every file git
    would commit under the benchmark's directories, but the record."""
    out = {}
    for top in HELD:
        for folder, dirs, names in os.walk(os.path.join(root, top)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in names:
                rel = os.path.relpath(os.path.join(folder, name), root).replace(os.sep, "/")
                if rel != RECORD and not name.endswith(".pyc"):
                    out[rel] = digest(os.path.join(folder, name))
    return dict(sorted(out.items()))


def take(root: str, about: str) -> dict:
    return {"about": about, "files": held_files(root), "benchmark": load(root, "BENCHMARK.json")}


def write(root: str, about: str) -> str:
    path = os.path.join(root, RECORD)
    with open(path, "w") as f:
        json.dump(take(root, about), f, indent=1)
        f.write("\n")
    return path


# -- the rules ---------------------------------------------------------------------


def every_accepted_file_is_there_byte_for_byte(root: str, accepted: dict) -> list:
    wrong = []
    for rel, was in accepted["files"].items():
        path = os.path.join(root, rel)
        if not os.path.isfile(path):
            wrong.append(f"{rel}: gone")
        elif digest(path) != was:
            wrong.append(f"{rel}: changed")
    return wrong


def every_scalar_key_is_equal(root: str, accepted: dict) -> list:
    was, now = accepted["benchmark"], load(root, "BENCHMARK.json")
    wrong = [f"key {key!r} came or went" for key in sorted(set(was) ^ set(now))]
    return wrong + [f"{key}: {now[key]!r} was {was[key]!r}" for key in was if key not in LISTS and key in now and now[key] != was[key]]


def accepted_entries_stand_first(which: str):
    def rule(root: str, accepted: dict) -> list:
        was, now = accepted["benchmark"][which], load(root, "BENCHMARK.json")[which]
        wrong = []
        for place, entry in enumerate(was):
            if place >= len(now):
                wrong.append(f"{which}[{place}] ({entry['name']}): gone")
            elif now[place] != entry:
                wrong.append(f"{which}[{place}]: {now[place].get('name')!r} stands where {entry['name']!r} stood, or it was edited")
        return wrong

    rule.__name__ = f"accepted_{which}_stand_first_in_place_as_they_were"
    return rule


def every_per_layer_entry_has_its_file_with_the_same_keys_and_values(root: str, _accepted: dict) -> list:
    wrong = []
    for entry in load(root, "BENCHMARK.json")["per_layer"]:
        path = os.path.join(root, METRICS_DIR, entry["name"] + ".json")
        if not os.path.isfile(path):
            wrong.append(f"{entry['name']}: no layer_metrics/{entry['name']}.json")
        elif {k: v for k, v in load(path).items() if k not in FILE_ONLY} != entry:
            wrong.append(f"{entry['name']}: its file and its entry differ")
    return wrong


def every_layer_metric_file_has_its_entry(root: str, _accepted: dict) -> list:
    entries = {m["name"] for m in load(root, "BENCHMARK.json")["per_layer"]}
    files = {name[: -len(".json")] for name in os.listdir(os.path.join(root, METRICS_DIR)) if name.endswith(".json")}
    return [f"layer_metrics/{name}.json has no entry of per_layer" for name in sorted(files - entries)]


def no_more_cells_than_a_check_can_run(root: str, _accepted: dict) -> list:
    cells = load(root, "BENCHMARK.json")["workloads"]
    return [f"{len(cells)} cells, at most {MAX_CELLS}"] if len(cells) > MAX_CELLS else []


def four_chip_cells_keep_to_a_quarter(root: str, accepted: dict) -> list:
    """A quarter of the cells, rounded down; one always may; and as many
    as the accepted benchmark has."""
    cells = load(root, "BENCHMARK.json")["workloads"]
    may = max(1, len(cells) // 4, sum(w["chips"] == 4 for w in accepted["benchmark"]["workloads"]))
    four = [w["name"] for w in cells if w["chips"] == 4]
    wrong = [f"{w['name']}: chips {w['chips']!r}" for w in cells if w["chips"] not in (1, 4)]
    return wrong + ([f"{len(four)} cells on four chips ({', '.join(four)}), at most {may}"] if len(four) > may else [])


def every_configuration_has_a_cell_and_every_cell_its_configuration(root: str, _accepted: dict) -> list:
    bench = load(root, "BENCHMARK.json")
    configs, used = {c["name"] for c in bench["configs"]}, {w["config"] for w in bench["workloads"]}
    wrong = [f"configuration {name}: no cell" for name in sorted(configs - used)]
    wrong += [f"cell {w['name']}: no configuration {w['config']}" for w in bench["workloads"] if w["config"] not in configs]
    return wrong + [f"configuration {c['name']}: no file {c['file']}" for c in bench["configs"] if not os.path.isfile(os.path.join(root, c["file"]))]


def every_source_fits_its_line(root: str, _accepted: dict) -> list:
    bench = load(root, "BENCHMARK.json")
    return [
        f"{which} {entry['name']}: a source of {len(entry['source'])} characters, at most {MAX_SOURCE}"
        for which in LISTS for entry in bench[which]
        if "source" in entry and not 1 <= len(entry["source"]) <= MAX_SOURCE
    ]


def no_two_entries_share_a_name(root: str, _accepted: dict) -> list:
    bench = load(root, "BENCHMARK.json")
    wrong = []
    for group in (("configs",), ("workloads",), ("end_to_end", "per_layer")):  # no two metrics, cells, configurations
        names = [entry["name"] for which in group for entry in bench[which]]
        wrong += [f"{'/'.join(group)}: {name} twice" for name in sorted(set(names)) if names.count(name) > 1]
    return wrong


RULES = {
    rule.__name__: rule
    for rule in (
        every_accepted_file_is_there_byte_for_byte,
        every_scalar_key_is_equal,
        *(accepted_entries_stand_first(which) for which in LISTS),
        every_per_layer_entry_has_its_file_with_the_same_keys_and_values,
        every_layer_metric_file_has_its_entry,
        no_more_cells_than_a_check_can_run,
        four_chip_cells_keep_to_a_quarter,
        every_configuration_has_a_cell_and_every_cell_its_configuration,
        every_source_fits_its_line,
        no_two_entries_share_a_name,
    )
}


def broken(root: str) -> dict:
    """Every rule the tree at `root` breaks, with what is wrong."""
    accepted = load(root, RECORD)
    found = {name: rule(root, accepted) for name, rule in RULES.items()}
    return {name: wrong for name, wrong in found.items() if wrong}


if __name__ == "__main__":
    repo = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    if len(sys.argv) == 3 and sys.argv[1] == "--write":
        print(write(repo, sys.argv[2]))
    else:
        found = broken(repo)
        print(json.dumps(found, indent=1))
        sys.exit(1 if found else 0)
