import json
import os

import numpy as np
import pytest

from perfbench.tests.helpers import REPO
from perfbench.traffic import general

MIXES = ["wmt14_pairs_b256"]
SIZES = json.load(open(os.path.join(REPO, "perfbench/configs/seqtoseq-wmt14.json")))


def _mix(name):
    return json.load(open(os.path.join(REPO, "perfbench/traffic", name + ".json")))


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_stream_other_seed_other(name):
    a = general.generate(_mix(name), SIZES, 2 ** 31 + 12345)
    b = general.generate(_mix(name), SIZES, 2 ** 31 + 12345)
    c = general.generate(_mix(name), SIZES, 7)
    for f in a.seq:
        assert np.array_equal(a.seq[f][0], b.seq[f][0])
        assert np.array_equal(a.seq[f][1], b.seq[f][1])
        assert not np.array_equal(a.seq[f][0][:1000], c.seq[f][0][:1000])


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_and_group_offers_the_same_lengths(name):
    a = general.generate(_mix(name), SIZES, 1)
    c = general.generate(_mix(name), SIZES, 2)
    for f in a.seq:
        base = sorted(a.lengths(0)[f])
        for g in (1, a.groups - 1):
            assert sorted(a.lengths(g)[f]) == base
            assert sorted(c.lengths(g)[f]) == base
        assert not np.array_equal(a.lengths(0)[f], c.lengths(0)[f])
        spec = _mix(name)["lengths"]["sentence"]
        assert min(base) >= spec["min"] and max(base) <= spec["max"]
        assert abs(np.median(base) - spec["median"]) <= 1


def test_shifted_field_and_padding():
    items = general.generate(_mix("wmt14_pairs_b256"), SIZES, 3)
    s = general.samples_of(items, 1)
    assert len(s) == 256
    for row in s[:20]:
        assert row["target_language_word"] == (
            [SIZES["bos_id"]] + row["target_language_next_word"][:-1])
        assert min(row["source_language_word"]) >= 2
    arr = general.arrays_of(items, 1)
    assert arr["source_language_word"].shape == (256, 128)
    assert items.shapes(1)["source_language_word"] == (128, 256)
    i = 5
    n = arr["target_language_word.len"][i]
    assert list(arr["target_language_word"][i, :n]) == s[i]["target_language_word"]
    assert not arr["target_language_word"][i, n:].any()


def test_bucket_is_the_trainers_rule():
    from paddle_tpu.data.feeder import bucket_length

    for n in list(range(1, 70)) + [100, 128, 129, 500, 512, 513]:
        assert general.bucket(n) == bucket_length(n)
