"""The one general traffic generator: a traffic file's parameters plus a
seed give the inputs of a run.

The schedule of a serving mix (sizes, their order, arrival gaps) is data:
it is drawn from the traffic file's ``schedule_seed``, and ``--seed`` draws
only what the requests hold, their token ids. A request lives for tens of
seconds on today's system, so a window holds a few dozen requests, and the
order in which long and short ones meet moved the completed-token rate by
5% between two seeds on the chip (PERF.md, PR 24): a seed that reorders
changes the work. Lengths are the quantile midpoints of their
distribution, dealt in rounds of ``round_size`` requests that each hold the
whole set once; arrival gaps are drawn per round and scaled so that each
round lasts exactly ``round_size / rate`` seconds.
"""

import math
from statistics import NormalDist

import numpy as np


def zipf_tokens(rng, shape, vocab_size, exponent):
    """Token ids with a Zipf law over the vocabulary (rank r has weight
    r ** -exponent), so a language model's loss can fall on them."""
    ranks = rng.zipf(exponent, size=shape)
    return ((ranks - 1) % vocab_size).astype(np.int32)


def train_batches(traffic, seed, n_sequences, seq_len, vocab_size):
    """Endless stream of ``{"input_ids": [n_sequences, seq_len]}``."""
    rng = np.random.default_rng([int(seed), 1])
    while True:
        yield {"input_ids": zipf_tokens(rng, (n_sequences, seq_len),
                                        vocab_size,
                                        traffic["zipf_exponent"])}


def lognormal_quantiles(spec, n):
    """The n quantile midpoints of a clipped lognormal, as whole numbers."""
    lo, hi = spec["clip"]
    z = [NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)]
    vals = [spec["median"] * math.exp(spec["sigma"] * zi) for zi in z]
    return np.clip(np.rint(vals), lo, hi).astype(np.int64)


def request_sizes(traffic, n_requests):
    """(prompt_len, output_len) for each of ``n_requests``, in rounds."""
    k = int(traffic["round_size"])
    prompts = lognormal_quantiles(traffic["prompt_len"], k)
    outputs = lognormal_quantiles(traffic["output_len"], k)
    rng = np.random.default_rng([int(traffic["schedule_seed"]), 2])
    outputs = outputs[rng.permutation(k)]   # pair prompts with answers
    sizes = []
    for _ in range(-(-n_requests // k)):
        order = rng.permutation(k)
        sizes += [(int(prompts[i]), int(outputs[i])) for i in order]
    return sizes[:n_requests]


def arrival_times(arrivals, schedule_seed, n_requests, round_size):
    """Due time of each request in seconds from the start of the load, or
    None for a closed loop (a request is due when its client's previous
    answer ended)."""
    kind = arrivals["kind"]
    if kind == "closed":
        return None
    rate = float(arrivals["rate_rps"])
    rng = np.random.default_rng([int(schedule_seed), 3])
    gaps = []
    for _ in range(-(-n_requests // round_size)):
        if kind == "poisson":
            g = rng.exponential(1.0, round_size)
        elif kind == "gamma":   # coefficient of variation ``cv`` > 1: bursts
            shape = 1.0 / float(arrivals["cv"]) ** 2
            g = rng.gamma(shape, 1.0 / shape, round_size)
        else:
            raise ValueError(f"unknown arrival kind {kind!r}")
        gaps.append(g * (round_size / rate) / g.sum())
    return np.cumsum(np.concatenate(gaps))[:n_requests]


def serve_requests(traffic, seed, n_requests, vocab_size):
    """The schedule of a serving run: a list of dicts with ``prompt``
    (int32 ids, no shared prefixes), ``max_new_tokens`` and ``due_s``."""
    sizes = request_sizes(traffic, n_requests)
    due = arrival_times(traffic["arrivals"], traffic["schedule_seed"],
                        n_requests, int(traffic["round_size"]))
    rng = np.random.default_rng([int(seed), 4])
    return [{"prompt": rng.integers(0, vocab_size, p, dtype=np.int32),
             "max_new_tokens": o,
             "due_s": None if due is None else float(due[i])}
            for i, (p, o) in enumerate(sizes)]
