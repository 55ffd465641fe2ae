"""The one general traffic generator: a traffic file's parameters in, the
work out. Lengths and their order come from the file alone; ``--seed``
decides token ids (and the weights) and nothing else, because in this
engine lengths and order ARE the work."""

import numpy as np


def quantile_lengths(spec: dict, n: int) -> list:
    """``n`` lengths: the evenly spaced quantiles of a log-uniform law on
    [min, max], put in order by the stride permutation i -> i*stride mod n
    (stride coprime with n): drawn from no RNG."""
    if spec["law"] != "log-uniform-quantiles":
        raise ValueError(f"unknown length law {spec['law']!r}")
    lo, hi, stride = spec["min"], spec["max"], int(spec["stride"])
    if np.gcd(stride, n) != 1:
        raise ValueError(f"stride {stride} is not coprime with {n}")
    q = lo * (hi / lo) ** ((np.arange(n) + 0.5) / n)
    return [int(round(q[(i * stride) % n])) for i in range(n)]


def request_shapes(traffic: dict) -> list:
    """[(prompt_len, output_len)] of the whole list, which cycles."""
    n = int(traffic["requests"])
    return list(zip(quantile_lengths(traffic["prompt_len"], n),
                    quantile_lengths(traffic["output_len"], n)))


def _rng(seed: int, index: int):
    return np.random.default_rng([int(seed), int(index)])


def prompt_ids(seed: int, index: int, length: int, vocab: int) -> np.ndarray:
    """Token ids of the ``index``-th request submitted (counting through
    the cycles), from the seed and the index alone."""
    return _rng(seed, index).integers(0, vocab, (length,), dtype=np.int32)


def train_batch_ids(seed: int, step: int, batch: int, seq: int,
                    vocab: int) -> np.ndarray:
    """The global batch of optimizer step ``step`` (0-based): rows that all
    differ, from the seed and the step alone."""
    return _rng(seed, step).integers(0, vocab, (batch, seq), dtype=np.int32)
