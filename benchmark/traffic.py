"""The one general traffic generator: a traffic mix is a file of
parameters under ``traffic/``, and this module turns it and a seed into
a schedule of requests (serving) or a pool of token batches (training).

Every seed offers the same load. The number of requests due in the
window is ``round(rate * seconds)``; their prompt and output lengths are
the quantile grid of the two length distributions (stratum ``i`` of
``n`` yields the ``(i + u) / n`` quantile, ``u`` a seeded jitter inside
the stratum); prompt stratum ``i`` is paired with output stratum
``i * k mod n``, a lattice that spreads the pairs evenly over the
square, the same under every seed (a seeded pairing moved the product
of prompt and output length summed over requests, which is what the
cache holds over time, by 2% from seed to seed); the gaps between
arrivals are the quantile grid of the exponential distribution, scaled
to fill the window. The seed decides the jitter, the order in which the
requests and the gaps come, and the token ids. So the multiset of
(prompt, output) pairs, the tokens offered and the multiset of gaps are
the same under every seed, to within the jitter.

Order matters as well as totals: the server's occupancy follows the
load of the last ten or twenty seconds, and the time per token follows
occupancy. So requests come in ``balanced_order`` (every few neighbours
hold the same mix of short and long), by default evenly paced with a
jitter, and a mix may fix the order and the arrival times for all runs
(``arrivals.order_seed``), leaving to the run's seed what does not
change the work; PERF.md has the measurements that led there.
"""

from __future__ import annotations

import json
import math
import os
from statistics import NormalDist

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
KINDS = ("open_loop", "closed_loop", "train_job")


def load(name: str) -> dict:
    """The traffic mix ``traffic/<name>.json``."""
    path = os.path.join(HERE, "traffic", f"{name}.json")
    with open(path) as f:
        mix = json.load(f)
    if mix.get("kind") not in KINDS:
        raise ValueError(f"{path}: kind {mix.get('kind')!r}, expected one "
                         f"of {KINDS}")
    return mix


def _rng(seed: int, tag: int):
    return np.random.default_rng([int(seed), int(tag)])


def length_quantile(dist: dict, q: float) -> int:
    """The ``q`` quantile of a clipped length distribution."""
    if dist["dist"] == "lognormal":
        raw = dist["median"] * math.exp(
            dist["sigma"] * NormalDist().inv_cdf(min(max(q, 1e-9),
                                                     1 - 1e-9)))
    elif dist["dist"] == "uniform":
        raw = dist["min"] + q * (dist["max"] - dist["min"])
    elif dist["dist"] == "fixed":
        raw = dist["value"]
    else:
        raise ValueError(f"length distribution {dist['dist']!r}")
    return int(min(max(round(raw), dist.get("min", 1)),
                   dist.get("max", 1 << 30)))


def stratified_lengths(dist: dict, n: int, rng) -> list:
    """``n`` lengths, one from each of ``n`` equal-probability strata,
    in stratum order."""
    jitter = rng.random(n)
    return [length_quantile(dist, (i + jitter[i]) / n) for i in range(n)]


def lattice_step(n: int) -> int:
    """A step coprime to ``n`` near ``n`` over the golden ratio: the
    pairs ``(i, i * k mod n)`` then fill the square evenly."""
    if n < 3:
        return 1
    k = max(int(round(n / 1.6180339887)), 1)
    while math.gcd(k, n) != 1:
        k += 1
    return k


def balanced_order(n: int, per_group: int, rng) -> list:
    """A seeded order of ``range(n)`` in which every run of about
    ``per_group`` neighbours holds indices spread evenly over the whole
    range: index ``i`` goes to group ``(i + r) mod m`` (``m`` groups, a
    seeded rotation ``r``), the groups follow one another and each is
    shuffled inside. With ``per_group`` 0 the order is a plain shuffle.

    Applied to requests sorted by length and to gaps sorted by size, it
    gives every stretch of the window the same mix of short and long,
    so the load offered is even over time as well as in total: the
    server's occupancy, which follows the load of the last ten or
    twenty seconds, then depends little on the seed."""
    if per_group <= 0 or n <= per_group:
        return [int(i) for i in rng.permutation(n)]
    m = max(int(round(n / per_group)), 1)
    rot = int(rng.integers(0, m))
    groups = [[] for _ in range(m)]
    for i in range(n):
        groups[(i + rot) % m].append(i)
    out = []
    for g in groups:
        out.extend(g[j] for j in rng.permutation(len(g)))
    return out


def arrival_times(n: int, span_s: float, rng, arrivals: dict = None) -> list:
    """``n`` arrival times in ``[0, span_s)``, sorted.

    ``arrivals["gaps"]`` chooses the process. ``even`` (the default):
    arrival ``k`` falls at ``(k + phase + jitter * (u_k - 1/2)) / n`` of
    the span, one seeded phase for all and a seeded ``u_k`` each; with
    ``jitter`` 0 the arrivals are evenly paced. ``exponential``: gaps on
    the exponential distribution's quantile grid, in seeded
    (``balanced_order``) order, a Poisson process conditioned on its
    count and its multiset of gaps. With ``burst`` (``on_s``, ``off_s``)
    the same arrivals are squeezed into the on-periods, at the same mean
    rate over the whole span."""
    if n == 0:
        return []
    arrivals = arrivals or {}
    burst = arrivals.get("burst")
    busy_span = span_s
    if burst:
        period = burst["on_s"] + burst["off_s"]
        busy_span = span_s * burst["on_s"] / period
    kind = arrivals.get("gaps", "even")
    if kind == "even":
        phase, u = rng.random(), rng.random(n)
        jitter = float(arrivals.get("jitter", 0.0))
        times = np.sort(((np.arange(n) + phase + jitter * (u - 0.5)) % n)
                        * busy_span / n)
    elif kind == "exponential":
        gaps = np.array([-math.log(1.0 - (k + 0.5) / n) for k in range(n)])
        gaps = gaps[balanced_order(
            n, int(arrivals.get("balance_group", 0)), rng)]
        gaps *= busy_span / gaps.sum()
        times = np.cumsum(gaps) - gaps[0] * rng.random()
    else:
        raise ValueError(f"arrivals.gaps {kind!r}: expected 'even' or "
                         "'exponential'")
    if burst:
        times = times + burst["off_s"] * np.floor(times / burst["on_s"])
    return [float(t) for t in times]


def shared_stems(mix: dict, seed: int, vocab: int) -> list:
    """The run's shared prompt prefixes (``sharing``: ``groups`` stems of
    ``prefix_tokens`` ids), none for a mix that shares nothing."""
    share = mix.get("sharing") or {}
    rng = _rng(seed, 3)
    return [rng.integers(0, vocab, share.get("prefix_tokens", 0)).tolist()
            for _ in range(share.get("groups", 0))]


def _requests(mix: dict, n: int, vocab: int, rng, stems=(),
              per_group: int = 0, order_rng=None) -> list:
    """``n`` (prompt ids, max_new) pairs on the stratified grid, in
    ``balanced_order``. ``rng`` (the run's seed) draws the token ids;
    ``order_rng`` draws the jitter inside each stratum and the order
    (the run's seed too, unless the mix fixes them)."""
    order_rng = order_rng or rng
    prompts = stratified_lengths(mix["prompt"], n, order_rng)
    outputs = stratified_lengths(mix["output"], n, order_rng)
    k = lattice_step(n)
    limit = mix.get("max_total", 1 << 30)
    out = []
    for i in range(n):
        p_len = prompts[i]
        new = min(outputs[(i * k) % n], limit - p_len)
        ids = rng.integers(0, vocab, p_len).tolist()
        if stems:
            stem = stems[int(rng.integers(0, len(stems)))][:p_len]
            ids[:len(stem)] = stem
        out.append((ids, int(new)))
    return [out[j] for j in balanced_order(n, per_group, order_rng)]


def serving_schedule(mix: dict, seed: int, seconds: float,
                     vocab: int) -> dict:
    """The schedule of one run. Times are seconds from the window's
    start; the lead-in's are negative.

    ``open_loop``: ``requests`` carry ``due``; the same process runs for
    ``lead_in_s`` before the window, so that occupancy has reached its
    steady value when it opens, and for ``lead_out_s`` after it, so that
    the window's last requests finish under the same load as its first
    (the generator stops sending once the window's requests have all
    ended). ``closed_loop``: they carry none, and ``clients`` callers take them in order, each sending
    its next when its last has ended."""
    lead_s = float(mix.get("lead_in_s", 0.0))
    tail_s = float(mix.get("lead_out_s", 0.0))
    stems = shared_stems(mix, seed, vocab)
    requests = []
    if mix["kind"] == "open_loop":
        rate = float(mix["rate_per_s"])
        arrivals = mix.get("arrivals") or {}
        group = int(arrivals.get("balance_group", 0))
        for tag, span, offset, in_window in (
                (1, lead_s, -lead_s, False), (2, seconds, 0.0, True),
                (4, tail_s, seconds, False)):
            n = int(round(rate * span))
            rng = _rng(seed, tag)
            # ``arrivals.order_seed`` fixes the lengths, their order and
            # the arrival times for every run; the run's seed then
            # decides the token ids and the weights, which do not
            # change the work
            fixed = arrivals.get("order_seed")
            order_rng = rng if fixed is None else _rng(fixed, tag)
            pairs = _requests(mix, n, vocab, rng, stems, group, order_rng)
            for (ids, new), t in zip(pairs, arrival_times(
                    n, span, order_rng, arrivals)):
                requests.append({"due": offset + t, "prompt": ids,
                                 "max_new": new, "in_window": in_window})
        requests.sort(key=lambda r: r["due"])
    elif mix["kind"] == "closed_loop":
        n = int(mix["requests"])
        for ids, new in _requests(mix, n, vocab, _rng(seed, 2), stems):
            requests.append({"due": None, "prompt": ids, "max_new": new,
                             "in_window": False})
    else:
        raise ValueError(f"{mix['kind']} is not a serving mix")
    for i, r in enumerate(requests):
        r["id"] = i
    return {"kind": mix["kind"], "clients": int(mix.get("clients", 0)),
            "lead_in_s": lead_s, "window_s": float(seconds),
            "drain_limit_s": float(mix["drain_limit_s"]),
            "requests": requests}


def offered(schedule: dict) -> dict:
    """What the window offers: request count and token sums."""
    win = [r for r in schedule["requests"] if r["in_window"]]
    return {"requests": len(win),
            "prompt_tokens": sum(len(r["prompt"]) for r in win),
            "output_tokens": sum(r["max_new"] for r in win),
            "kv_token_steps": sum(
                (len(r["prompt"]) + r["max_new"] / 2.0) * r["max_new"]
                for r in win)}


# ---------------------------------------------------------------------
# training: a Markov-chain language (copied from the program's
# datasets/markov.py, which stays the original; see PERF.md)
# ---------------------------------------------------------------------
def make_chain(vocab: int, seed: int, concentration: float = 1.5):
    """A random row-stochastic transition matrix [V, V]."""
    rng = _rng(seed, 11)
    logits = concentration * rng.standard_normal((vocab, vocab))
    logits -= logits.max(axis=1, keepdims=True)
    p = np.exp(logits)
    return p / p.sum(axis=1, keepdims=True)


def sample_chain(p: np.ndarray, n_seq: int, seq_len: int, rng):
    """[n_seq, seq_len + 1] token ids drawn from the chain."""
    vocab = p.shape[0]
    cum = np.cumsum(p, axis=1)
    cum[:, -1] = 1.0
    toks = np.empty((n_seq, seq_len + 1), np.int32)
    toks[:, 0] = rng.integers(0, vocab, n_seq)
    u = rng.random((n_seq, seq_len))
    for t in range(seq_len):
        toks[:, t + 1] = (cum[toks[:, t]] < u[:, t:t + 1]).sum(axis=1)
    return np.minimum(toks, vocab - 1)


def train_pool(mix: dict, seed: int, vocab: int) -> np.ndarray:
    """The job's token pool [pool_batches, batch, seq_len + 1]: every
    row a fresh draw, so no two rows of the checked steps agree. The
    window cycles through the pool."""
    n, b, t = mix["pool_batches"], mix["batch"], mix["seq_len"]
    toks = sample_chain(make_chain(vocab, seed), n * b, t, _rng(seed, 12))
    return toks.reshape(n, b, t + 1)
