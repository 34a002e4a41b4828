"""The traffic generator: same seed, same schedule; every seed the same
sizes and gaps in the same order with other token ids; the stated clipping."""

import json
import os

import pytest

from . import _paths
from benchlib import traffic

TRAFFIC_DIR = os.path.join(_paths.BENCH_DIR, "traffic")
MIXES = sorted(f[:-5] for f in os.listdir(TRAFFIC_DIR) if f.endswith(".json"))


def sizes(schedule):
    return [(len(r.prompt), r.max_new_tokens) for r in schedule.requests]


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_schedule(mix):
    spec = traffic.load(traffic.find(TRAFFIC_DIR, mix))
    a = traffic.build(spec, 2147483999, 45, 32768)
    b = traffic.build(spec, 2147483999, 45, 32768)
    assert [(r.due_s, r.prompt, r.max_new_tokens) for r in a.requests] == [(r.due_s, r.prompt, r.max_new_tokens) for r in b.requests]


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_gets_the_same_work_with_other_token_ids(mix):
    spec = traffic.load(traffic.find(TRAFFIC_DIR, mix))
    a, b = traffic.build(spec, 1, 45, 32768), traffic.build(spec, 3_000_000_000, 45, 32768)
    assert [(r.due_s, len(r.prompt), r.max_new_tokens) for r in a.requests] == [(r.due_s, len(r.prompt), r.max_new_tokens) for r in b.requests]
    assert all(x.prompt != y.prompt for x, y in zip(a.requests, b.requests))
    if a.loop == "open":
        assert all(0 < r.due_s < 45 for r in a.requests)


@pytest.mark.parametrize("mix", MIXES)
def test_lengths_keep_to_the_stated_clipping_and_the_vocabulary(mix):
    spec = traffic.load(traffic.find(TRAFFIC_DIR, mix))
    sched = traffic.build(spec, 5, 45, 1000)
    p, o = spec["prompt_tokens"], spec["output_tokens"]
    assert all(p["min"] <= len(r.prompt) <= p["max"] for r in sched.requests)
    assert all(o["min"] <= r.max_new_tokens <= o["max"] for r in sched.requests)
    assert all(0 <= t < 1000 for r in sched.requests for t in r.prompt)


def test_chat_steady_is_an_open_loop_at_its_written_rate():
    spec = traffic.load(traffic.find(TRAFFIC_DIR, "chat-steady"))
    sched = traffic.build(spec, 9, 45, 32768)
    assert sched.loop == "open"
    assert len(sched.requests) == pytest.approx(45 * spec["arrival"]["rate_per_s"], rel=0.35)
    twice = traffic.build(spec, 9, 45, 32768, rate_scale=2.0)
    assert len(twice.requests) > 1.5 * len(sched.requests)


def test_longdoc_is_a_closed_loop_whose_queue_starts_over_with_new_ids():
    spec = traffic.load(traffic.find(TRAFFIC_DIR, "longdoc-saturated"))
    sched = traffic.build(spec, 9, 45, 64000)
    assert sched.loop == "closed" and sched.clients == 32 and len(sched.requests) == 32
    again = traffic.refill(sched, spec, 9, 1, 64000)
    assert sizes(sched) == [(len(r.prompt), r.max_new_tokens) for r in again]
    assert all(a.prompt != b.prompt for a, b in zip(sched.requests, again))
    assert {r.index for r in again}.isdisjoint({r.index for r in sched.requests})


def test_a_parameter_the_generator_does_not_know_is_refused():
    spec = traffic.load(traffic.find(TRAFFIC_DIR, "chat-steady"))
    with pytest.raises(ValueError):
        traffic.build({**spec, "arrival": {"process": "gamma", "rate_per_s": 1.0, "cv": 2.0}}, 1, 45, 100)
    with pytest.raises(ValueError):
        traffic.build({**spec, "prompt_tokens": {"dist": "zipf", "min": 1, "max": 2}}, 1, 45, 100)


def test_an_unknown_loop_is_refused(tmp_path):
    path = tmp_path / "x.json"
    path.write_text(json.dumps({"loop": "sideways"}))
    with pytest.raises(ValueError):
        traffic.load(str(path))
