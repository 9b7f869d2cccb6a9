"""The serving mixes' requests: the same for a seed, the same sizes in
another order for another seed, and within the configuration's context."""

import json
import os

import numpy as np
import pytest

import benchkit
from benchlib import traffic as T

BIG = 2 ** 31 + 12345
MIXES = ["minitron-longprompt", "minitron-offline"]


@pytest.mark.parametrize("mix", MIXES)
def test_a_seed_repeats_its_requests(mix):
    spec = T.load(mix)
    a, b = T.serve_plan(spec, BIG, 51, 256000), T.serve_plan(spec, BIG, 51,
                                                             256000)
    assert np.array_equal(a.prompt_len, b.prompt_len)
    assert np.array_equal(a.max_new, b.max_new)
    assert (a.due is None) == (spec["arrivals"] == "backlog")
    if a.due is not None:
        assert np.array_equal(a.due, b.due)
    assert np.array_equal(a.prompt(3), b.prompt(3))


@pytest.mark.parametrize("mix", MIXES)
def test_another_seed_orders_the_same_sizes_otherwise(mix):
    spec = T.load(mix)
    a, b = T.serve_plan(spec, BIG, 51, 256000), T.serve_plan(spec, BIG + 1,
                                                             51, 256000)
    assert sorted(a.prompt_len) == sorted(b.prompt_len)
    assert sorted(a.max_new) == sorted(b.max_new)
    assert not np.array_equal(a.prompt_len, b.prompt_len)
    assert not np.array_equal(a.prompt(0)[:8], b.prompt(0)[:8])
    if a.due is not None:
        assert np.allclose(np.sort(np.diff(a.due)), np.sort(np.diff(b.due))) \
            or len(a) > 2


def test_poisson_arrivals_fill_the_lead_and_the_window():
    spec = T.load("minitron-longprompt")
    p = T.serve_plan(spec, BIG, 51, 256000)
    span = spec["lead_seconds"] + 51
    assert len(p) == round(spec["rate_per_s"] * span)
    assert p.due[0] == 0 and p.due[-1] < span
    assert np.all(np.diff(p.due) > 0)


@pytest.mark.parametrize("mix", MIXES)
def test_every_request_fits_the_served_context(mix):
    with open(os.path.join(benchkit.BENCH, "configs",
                           "minitron-4b.json")) as f:
        cfg = json.load(f)
    spec = T.load(mix)
    assert spec["prompt_len"]["max"] + spec["output_len"]["max"] \
        <= cfg["max_len"]
    p = T.serve_plan(spec, BIG, 51, cfg["vocab_size"])
    assert p.prompt(0).dtype == np.int32
    assert 0 <= p.prompt(0).min() and p.prompt(0).max() < cfg["vocab_size"]
