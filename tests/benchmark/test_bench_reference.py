"""The plain reference against the program at a size a test run can hold
(`tiny`, CPU): the weights it makes from the seed are the program's, what
the engine serves lies within the limit, and the lower-precision control
comes out as not correct."""

import json
import os

import pytest

from . import _paths
from benchlib import reference

with open(os.path.join(_paths.FIXTURES, "tinyroot", "benchmark", "configs", "tiny.json")) as f:
    TINY = json.load(f)
LIMIT = TINY["correct"]["limits"]["logit_gap_max"]


def served(seed, alter=None):
    """Four requests through the program's own engine; what it streamed."""
    import jax
    import numpy as np

    from modal_tpu.models.llama import get_config, init_params
    from modal_tpu.serving.engine import ServingEngine

    cfg = get_config("tiny")
    engine = ServingEngine(init_params(cfg, jax.random.PRNGKey(seed)), cfg, max_slots=4).start()
    try:
        rng = np.random.default_rng(seed)
        prompts = [[int(x) for x in rng.integers(0, 512, size=n)] for n in (150, 60, 100, 30)]
        handles = [engine.submit(p, 40) for p in prompts]
        return [{"prompt": p, "tokens": h.result(timeout=120)} for p, h in zip(prompts, handles)]
    finally:
        engine.stop()


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5])
def test_the_reference_makes_the_program_s_weights_from_the_seed_alone(seed):
    import jax

    from modal_tpu.models.llama import get_config, init_params

    mine = reference.init_weights(TINY, seed)
    theirs = init_params(get_config("tiny"), jax.random.PRNGKey(seed & 0x7FFFFFFF))
    assert jax.tree_util.tree_structure(mine) == jax.tree_util.tree_structure(theirs)
    same = jax.tree_util.tree_map(lambda a, b: a.dtype == b.dtype and bool((a == b).all()), mine, theirs)
    assert all(jax.tree_util.tree_leaves(same))


def test_the_reference_imports_nothing_of_the_program():
    with open(reference.__file__) as f:
        source = f.read()
    assert "modal_tpu" not in source.replace("the program", "")


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_served_tokens_lie_within_the_limit_and_the_fp8_control_does_not(seed):
    ref = reference.Reference(TINY, seed)
    out = reference.compare(ref, served(seed), control="fp8")
    assert out["tokens_compared"] == 160 and out["requests_compared"] == 4
    assert out["logit_gap_max"] <= LIMIT
    # the control: the reference itself with float8 operands, put in the
    # program's place, has to come out as NOT correct, with room
    assert out["control_logit_gap_max"] > 3 * LIMIT


def test_a_token_altered_in_a_stream_reads_far_over_the_limit():
    ref = reference.Reference(TINY, 4)
    requests = served(4)
    requests[2]["tokens"][5] = (requests[2]["tokens"][5] + 1) % 512
    out = reference.compare(ref, requests)
    assert out["logit_gap_max"] > 10 * LIMIT
    assert out["per_request"][2]["gap_max"] == out["logit_gap_max"]


def test_the_gap_is_read_at_the_position_that_produced_each_token():
    import numpy as np

    logits = np.array([[0.0, 2.0, 1.0], [3.0, 0.0, 2.5]], np.float32)
    assert reference.served_gap(logits, [1, 2]) == [0.0, 0.5]
