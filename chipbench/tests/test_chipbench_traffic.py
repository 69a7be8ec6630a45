"""The traffic generator and the tail and rate arithmetic."""
import math

import numpy as np
import pytest

from chipbench.lib import gen, harness, traffic

CFG = harness.load_json(harness.BENCH / "configs" / "table3.json")
BIG = 2 ** 31 + 12345  # seeds run past 32 signed bits
TABLE3_50_TRS = 758  # TRs in the first 50 Table 3 sequences of seed 0


def test_arrivals_are_fixed_by_the_seed_and_their_count_by_the_mix():
    mix = {"kind": "serve", "arrivals": "poisson", "rate_per_s": 40}
    a = traffic.arrival_times(mix, 2.5, BIG)
    assert np.array_equal(a, traffic.arrival_times(mix, 2.5, BIG))
    b = traffic.arrival_times(mix, 2.5, BIG + 1)
    assert len(a) == len(b) == 100 and not np.array_equal(a, b)
    assert np.all(np.diff(a) >= 0) and a[0] >= 0 and a[-1] < 2.5


def test_requests_are_fixed_by_the_seed_fresh_and_distinct():
    mix = {"kind": "serve", "rate_per_s": 1}
    a = traffic.requests(CFG, mix, 30, BIG)
    assert a == traffic.requests(CFG, mix, 30, BIG)
    assert a != traffic.requests(CFG, mix, 30, BIG + 1)
    assert len(set(a)) == 30


def test_zipf_repeats_are_fixed_by_the_seed_and_repeat():
    mix = {"kind": "serve", "rate_per_s": 1,
           "repeats": {"pool": 16, "zipf_s": 1.1}}
    a = traffic.requests(CFG, mix, 200, BIG)
    assert a == traffic.requests(CFG, mix, 200, BIG)
    assert len(set(a)) <= 16


def test_generator_output_is_pinned():
    """The yardstick's data: a change to the generator changes this."""
    db = gen.generate("table3", 0, db_size=50)
    assert sum(len(it) for s in db for it in s) == TABLE3_50_TRS


@pytest.mark.parametrize(
    "name", [c["name"] for c in harness.load_json(
        harness.ROOT / "BENCHMARK.json")["configs"]])
def test_each_config_generator_is_found_by_name_and_sized_by_its_key(name):
    cfg = harness.load_json(harness.BENCH / "configs" / f"{name}.json")
    data = cfg["data"]
    assert data["size_key"] in data["params"]
    assert (harness.BENCH / "generators" / f"{data['generator']}.py").exists()
    a = gen.database(cfg, BIG, 7)
    assert len(a) == 7 and a == gen.database(cfg, BIG, 7)


def test_percentile_counts_failures_as_misses_over_all_requests():
    assert traffic.percentile([1.0] * 95 + [math.inf] * 5, 95) == 1.0
    assert traffic.percentile([1.0] * 94 + [math.inf] * 6, 95) == math.inf
    assert traffic.percentile(list(range(1, 101)), 95) == 95
    assert traffic.percentile([], 95) == math.inf


def test_serve_readers_take_the_tail_and_rate_over_the_whole_window():
    p95 = harness.load_module("metrics", "serve_p95_s")
    qps = harness.load_module("metrics", "serve_qps")
    lat = [0.1] * 90 + [5.0] * 5 + [math.inf] * 5
    assert p95.read({"latency_s": lat}) == 5.0
    assert qps.read({"exact_answers": 80, "served_s": 4.0}) == 20.0
    assert p95.read({}) is None and qps.read({}) is None
