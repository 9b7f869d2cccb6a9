"""The traffic generator: the same inputs for a seed, others for another
seed, and every mix the benchmark names loads."""

import json

import numpy as np
import pytest

import benchkit
from benchlib import traffic as T

BIG = 2 ** 31 + 12345


def test_climate_batches_follow_the_seed():
    a = T.climate_batch(BIG, 0, 1, (32, 48), 16, 3)
    b = T.climate_batch(BIG, 0, 1, (32, 48), 16, 3)
    c = T.climate_batch(BIG, 1, 1, (32, 48), 16, 3)
    assert np.array_equal(a["images"], b["images"])
    assert not np.array_equal(a["images"], c["images"])
    lab, img = a["labels"], a["images"][..., 0]
    assert np.array_equal(lab == 1, img > 1.2)
    assert np.array_equal(lab == 2, img < -1.2)


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 1, 2 ** 40 + 3])
def test_every_seed_asks_for_the_same_sizes(seed):
    b = T.climate_batch(seed, 2, 2, (32, 48), 16, 3)
    assert b["images"].shape == (2, 32, 48, 16)
    assert b["images"].dtype == np.float32
    assert b["labels"].shape == (2, 32, 48) and b["labels"].dtype == np.int32
    # smooth at 1/8 resolution: each 8x8 block holds one value
    img = b["images"][0, :8, :8, 0]
    assert np.all(img == img[0, 0])


def test_other_seed_other_images():
    a = T.climate_batch(BIG, 0, 1, (32, 48), 16, 3)
    b = T.climate_batch(BIG + 1, 0, 1, (32, 48), 16, 3)
    assert not np.array_equal(a["images"], b["images"])


def test_negative_seed_is_refused():
    with pytest.raises(ValueError):
        T.rng_for(-1, "images0")


def test_a_mix_names_its_kind(tmp_path):
    (tmp_path / "nokind.json").write_text(json.dumps({"global_batch": 1}))
    with pytest.raises(ValueError):
        T.load("nokind", str(tmp_path))


def test_every_mix_in_the_benchmark_loads():
    bm = benchkit.harness.load_benchmark()
    for cell in bm["workloads"]:
        _cell, entry = benchkit.harness.find_cell(bm, cell["name"])
        runner = benchkit.harness.load_config(entry)["runner"]
        assert T.load(cell["traffic"])["kind"] == runner
