"""The generator: same seed, same inputs; no two histories alike; the
invalid ones are there and proven invalid, the others proven valid."""

import json

import pytest
import reference
from traffic import generate as gen

KEYED = {"keys": 6, "ops_per_key": 300, "processes_per_key": 10, "values": 5,
         "crashes_per_key": {"read": 1, "write": 1, "cas": 1},
         "concurrent_keys": 5, "model": "cas-register"}
ONE = {"keys": 1, "ops_per_key": 4000, "processes_per_key": 5, "values": 5,
       "crashes_per_key": {"read": 2, "write": 2, "cas": 2}, "model": "cas-register"}
SEED = 2**31 + 4321


def pool(cfg, seed):
    return [gen.history(cfg, seed, h, 0 if h == 3 else None, (0.05, 0.06))
            for h in range(4)]


@pytest.mark.parametrize("cfg", [KEYED, ONE])
def test_same_seed_same_pool(cfg):
    assert pool(cfg, SEED) == pool(cfg, SEED)
    assert pool(cfg, SEED) != pool(cfg, SEED + 1)


@pytest.mark.parametrize("cfg", [KEYED, ONE])
def test_pool_histories_distinct_and_proven(cfg):
    p = pool(cfg, SEED)
    seen = {json.dumps(item["ops"]) for per_key in p for item in per_key.values()}
    assert len(seen) == len(p) * cfg["keys"]
    verdicts = [[reference.decide(i) for i in per_key.values()] for per_key in p]
    assert [all(v) for v in verdicts] == [True, True, True, False]
    for per_key in p:
        for item in per_key.values():
            if "order" in item:
                assert reference.proves_valid(item["ops"], item["order"])
            else:
                assert reference.proves_lost_write(item["ops"], item["lost_read"])


def test_window_is_fixed_by_the_crash_count():
    """Every crashed write or cas holds a slot to the end: the window
    ends at processes + crashed non-reads on every seed."""
    for seed in range(5):
        ops, _ = gen.key_history(ONE, gen.rng_for(seed, 0))
        open_, crashed, width = set(), 0, 0
        for o in ops:
            if o["type"] == "invoke":
                open_.add(o["process"])
            else:
                open_.discard(o["process"])
                crashed += o["type"] == "info" and o["f"] != "read"
            width = max(width, len(open_) + crashed)
        assert crashed == 4
        assert width == ONE["processes_per_key"] + crashed


def test_interleave_keeps_each_key_in_order():
    p = pool(KEYED, SEED)[0]
    per_key = {k: v["ops"] for k, v in p.items()}
    merged = gen.interleave(KEYED, per_key, gen.rng_for(SEED, 99))
    assert len(merged) == sum(len(v) for v in per_key.values())
    for k, ops in per_key.items():
        assert [dict(o, process=o["process"] - k * 1000)
                for kk, o in merged if kk == k] == ops
