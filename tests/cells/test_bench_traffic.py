"""The traffic generator offers the same load under every seed."""

import json
import math
import os
from collections import Counter

import pytest

from benchmark import common, traffic

SEEDS = [0, 1, 2, 3, 7, 11, 1234, 99991, 2**31 + 5, 3000000019]
MIX = traffic.load("chat-steady")
SECONDS, VOCAB = 51.0, 512
N = int(round(MIX["rate_per_s"] * SECONDS))


#: the same mix with the order left to the run's seed
FREE = dict(MIX, arrivals={k: v for k, v in MIX["arrivals"].items()
                           if k != "order_seed"})


def schedule(seed, mix=FREE):
    return traffic.serving_schedule(mix, seed, SECONDS, VOCAB)


def window(seed, mix=FREE):
    return [r for r in schedule(seed, mix)["requests"] if r["in_window"]]


@pytest.mark.parametrize("seed", SEEDS[1:])
def test_the_shipped_mix_offers_every_seed_the_same_schedule(seed):
    """``order_seed``: lengths, order and due times are the same in
    every run; the run's seed draws the token ids."""
    a, b = schedule(SEEDS[0], MIX), schedule(seed, MIX)
    shape = [(r["due"], len(r["prompt"]), r["max_new"], r["in_window"])
             for r in a["requests"]]
    assert shape == [(r["due"], len(r["prompt"]), r["max_new"],
                      r["in_window"]) for r in b["requests"]]
    assert [r["prompt"] for r in a["requests"]] != [
        r["prompt"] for r in b["requests"]]
    assert traffic.offered(a) == traffic.offered(b)


def test_the_shipped_mix_is_one_of_the_seeded_family():
    """Its fixed schedule has the count, the strata and the pacing every
    seeded schedule of the same mix has."""
    reqs = window(1, MIX)
    assert len(reqs) == N
    lengths = sorted(len(r["prompt"]) for r in reqs)
    for rank, length in enumerate(lengths):
        assert rank in stratum_of(MIX["prompt"], N, length)


def stratum_of(dist, n, length):
    """Every stratum a clipped length can have come from."""
    return {i for i in range(n)
            if traffic.length_quantile(dist, i / n) <= length
            <= traffic.length_quantile(dist, (i + 1) / n)}


@pytest.mark.parametrize("seed", SEEDS)
def test_same_request_count_under_every_seed(seed):
    assert len(window(seed)) == N
    rest = [r for r in schedule(seed)["requests"] if not r["in_window"]]
    assert len([r for r in rest if r["due"] < 0]) == int(round(
        MIX["rate_per_s"] * MIX["lead_in_s"]))
    assert len([r for r in rest if r["due"] >= SECONDS]) == int(round(
        MIX["rate_per_s"] * MIX["lead_out_s"]))


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("field", ["prompt", "output"])
def test_one_length_from_each_stratum(seed, field):
    reqs = window(seed)
    lengths = sorted(len(r["prompt"]) if field == "prompt"
                     else r["max_new"] for r in reqs)
    for rank, length in enumerate(lengths):
        assert rank in stratum_of(MIX[field], N, length), (rank, length)


@pytest.mark.parametrize("seed", SEEDS)
def test_due_times_lie_inside_the_window(seed):
    sched = schedule(seed)
    for r in sched["requests"]:
        if r["in_window"]:
            assert 0.0 <= r["due"] < SECONDS
        else:
            assert (-MIX["lead_in_s"] <= r["due"] < 0.0 or SECONDS
                    <= r["due"] < SECONDS + MIX["lead_out_s"])
    dues = [r["due"] for r in sched["requests"]]
    assert dues == sorted(dues)


@pytest.mark.parametrize("seed", SEEDS[1:])
def test_seed_changes_the_order_not_the_load(seed):
    a, b = traffic.offered(schedule(SEEDS[0])), traffic.offered(
        schedule(seed))
    assert a["requests"] == b["requests"]
    for key in ("prompt_tokens", "output_tokens", "kv_token_steps"):
        assert abs(a[key] - b[key]) / a[key] < 0.01, key
    first = [len(r["prompt"]) for r in window(SEEDS[0])]
    other = [len(r["prompt"]) for r in window(seed)]
    assert first != other


@pytest.mark.parametrize("seed", SEEDS[1:])
def test_same_multiset_of_gaps(seed):
    def gaps(s):
        rng = traffic._rng(s, 2)
        t = traffic.arrival_times(N, SECONDS, rng,
                                  {"gaps": "exponential"})
        return sorted(round(b - a, 9) for a, b in zip(t, t[1:]))

    # all but the gap the random start offset cuts are the same grid
    a, b = Counter(gaps(SEEDS[0])), Counter(gaps(seed))
    assert sum((a - b).values()) <= 1


@pytest.mark.parametrize("seed", SEEDS[:4])
def test_same_seed_same_schedule(seed):
    assert json.dumps(schedule(seed)) == json.dumps(schedule(seed))


@pytest.mark.parametrize("n", [1, 2, 3, 10, 97, 100, 255, 256])
def test_lattice_step_is_coprime(n):
    k = traffic.lattice_step(n)
    assert math.gcd(k, n) == 1
    assert len({(i * k) % n for i in range(n)}) == n


def test_no_request_passes_the_context_or_stops_on_eos():
    for r in window(5):
        assert len(r["prompt"]) + r["max_new"] <= MIX["max_total"]
        assert set(r) == {"due", "prompt", "max_new", "in_window", "id"}
    src = open(os.path.join(os.path.dirname(traffic.__file__),
                            "loadgen.py")).read()
    assert "eos_id" not in src


def test_bursts_keep_the_mean_rate():
    rng = traffic._rng(3, 2)
    t = traffic.arrival_times(200, 40.0, rng, {
        "gaps": "exponential", "burst": {"on_s": 2.0, "off_s": 2.0}})
    assert len(t) == 200 and 0.0 <= min(t) and max(t) < 40.0
    assert all((x % 4.0) < 2.0 + 1e-9 for x in t)


@pytest.mark.parametrize("seed", SEEDS[:5])
def test_paced_arrivals_put_the_same_count_in_every_stretch(seed):
    """Evenly paced with half a gap of jitter: any tenth of the window
    holds a tenth of the requests, to within two."""
    dues = [r["due"] for r in window(seed)]
    for k in range(10):
        got = sum(1 for d in dues
                  if k * SECONDS / 10 <= d < (k + 1) * SECONDS / 10)
        assert abs(got - N / 10) <= 2, (k, got)


@pytest.mark.parametrize("seed", SEEDS[:5])
def test_every_stretch_of_the_window_gets_the_same_mix(seed):
    """Balanced order: any run of 8 requests in arrival order offers
    8/N of the window's prompt and output tokens to within 70%, which
    is what the one longest request adds to its run (a plain shuffle of
    these heavy-tailed lengths read 0.59 to 1.10 over six seeds)."""
    reqs = window(seed)
    total_p = sum(len(r["prompt"]) for r in reqs)
    total_o = sum(r["max_new"] for r in reqs)
    worst = 0.0
    for lo in range(0, N - 8, 4):
        run = reqs[lo:lo + 8]
        worst = max(worst, abs(sum(len(r["prompt"]) for r in run)
                               / (total_p * 8 / N) - 1.0),
                    abs(sum(r["max_new"] for r in run)
                        / (total_o * 8 / N) - 1.0))
    assert worst < 0.7, worst


@pytest.mark.parametrize("n,group", [(10, 0), (10, 4), (115, 4),
                                      (45, 4), (7, 8)])
def test_balanced_order_is_a_permutation(n, group):
    order = traffic.balanced_order(n, group, traffic._rng(1, 1))
    assert sorted(order) == list(range(n))


def test_unknown_arrival_process_is_an_error():
    with pytest.raises(ValueError):
        traffic.arrival_times(5, 1.0, traffic._rng(1, 1),
                              {"gaps": "fractal"})


def test_shared_prefixes():
    mix = dict(MIX, sharing={"groups": 2, "prefix_tokens": 24})
    reqs = [r for r in traffic.serving_schedule(
        mix, 4, 20.0, VOCAB)["requests"] if len(r["prompt"]) >= 24]
    stems = {tuple(r["prompt"][:24]) for r in reqs}
    assert len(stems) == 2


def test_closed_loop_schedule_has_no_due_times():
    mix = {"kind": "closed_loop", "clients": 4, "requests": 30,
           "lead_in_s": 1.0, "drain_limit_s": 5.0,
           "prompt": {"dist": "uniform", "min": 10, "max": 20},
           "output": {"dist": "fixed", "value": 4}}
    sched = traffic.serving_schedule(mix, 1, 5.0, 32)
    assert sched["clients"] == 4 and len(sched["requests"]) == 30
    assert all(r["due"] is None and r["max_new"] == 4
               for r in sched["requests"])


@pytest.mark.parametrize("seed", SEEDS[:3])
def test_train_pool_rows_all_differ(seed):
    mix = {"pool_batches": 3, "batch": 4, "seq_len": 16}
    pool = traffic.train_pool(mix, seed, 32)
    assert pool.shape == (3, 4, 17)
    rows = {tuple(r) for r in pool.reshape(12, 17).tolist()}
    assert len(rows) == 12
    assert pool.min() >= 0 and pool.max() < 32
    # the block's adapter one-hots a batch for the program's net
    f, y = common.load_by_path("models", "cgpt_block").encode_batch(
        pool[0], {"vocab_size": 32})
    assert f.shape == y.shape == (4, 32, 16)
    assert (f.argmax(axis=1) == pool[0][:, :-1]).all()
    assert (y.argmax(axis=1) == pool[0][:, 1:]).all()
