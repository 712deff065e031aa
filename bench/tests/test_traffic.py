"""Seeded traffic: the same seed gives the same traffic, another seed
another order and other ids over the same rows, and the program's packer
packs every row whole, so every seed trains as many tokens a step."""
import numpy as np

import traffic

SPEC = {"num_records": 64, "length_seed": 7,
        "prompt_len": {"median": 20, "sigma": 0.6, "min": 4, "max": 64},
        "completion_len": {"median": 30, "sigma": 0.7, "min": 4, "max": 64}}
ROW = 96
BIG = 2 ** 31 + 12345


def _all(gen):
    return [np.concatenate(gen.arrays(i)) for i in range(gen.num_records)]


def test_sft_records_same_seed_same_traffic():
    a = traffic.SftRecords(SPEC, BIG, 1000, ROW)
    b = traffic.SftRecords(SPEC, BIG, 1000, ROW)
    assert all(np.array_equal(x, y) for x, y in zip(_all(a), _all(b)))


def test_sft_records_other_seed_same_lengths_other_order():
    a = traffic.SftRecords(SPEC, BIG, 1000, ROW)
    b = traffic.SftRecords(SPEC, BIG + 1, 1000, ROW)
    assert a.num_records == b.num_records >= SPEC["num_records"]
    assert not np.array_equal(a.lengths, b.lengths)
    assert sorted(map(tuple, a.lengths)) == sorted(map(tuple, b.lengths))
    assert not all(np.array_equal(x, y) for x, y in zip(_all(a), _all(b)))
    p, c = a.arrays(3)
    assert p[0] == 1 and len(p) >= 4 and len(c) >= 4
    assert np.all(np.concatenate([p[1:], c]) >= 3)
    assert np.all(np.concatenate([p, c]) < 1000)


def test_rows_are_full_and_lengths_in_range():
    rows = traffic.full_rows(SPEC, ROW)
    assert all(sum(p + c for p, c in r) == ROW for r in rows)
    lens = np.array([pc for r in rows for pc in r])
    assert lens[:, 0].min() >= 4 and lens[:, 1].min() >= 4
    assert lens[:, 0].max() <= 64


def test_the_packer_trains_as_many_tokens_a_step_for_every_seed():
    from repro.data.pipeline import SFTPipeline
    from repro.data.pipeline.records import Record

    class Source:
        def __init__(self, gen):
            self.gen, self.num_records = gen, gen.num_records

        def record_at(self, i):
            p, c = self.gen.arrays(i)
            return Record(prompt=p, completion=c)

    for seed in (0, BIG, BIG + 1):
        feed = SFTPipeline(Source(traffic.SftRecords(SPEC, seed, 1000, ROW)),
                           seq_len=ROW, global_batch=4, pack=True)
        cur = 0
        for _ in range(12):                 # past the end of the pool too
            batch, cur = feed.build(cur)
            assert np.count_nonzero(batch["segment_ids"]) == 4 * ROW
