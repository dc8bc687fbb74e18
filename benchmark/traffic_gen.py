"""The one traffic generator: turns a traffic file's parameters and the seed
into requests and arrival times. A new mix is a new data file, never code.

Every seed gets the same multiset of sizes (and, for an open loop, of gaps
between arrivals) in another order, with other residues: the seed changes
the inputs and never the amount of work, so runs of different seeds can be
compared.
"""

from __future__ import annotations

import numpy as np

NUM_AMINO_ACIDS = 21        # token ids 0..20, as the model's embedding has


def _rng(seed: int, stream: int):
    return np.random.default_rng([int(seed), int(stream)])


def fold_request(rng, length: int, msa_depth: int):
    from alphafold2_tpu.serve import FoldRequest
    return FoldRequest(
        rng.integers(0, NUM_AMINO_ACIDS, size=(length,)),
        msa=rng.integers(0, NUM_AMINO_ACIDS, size=(msa_depth, length)))


def closed_loop_requests(seed: int, lengths, msa_depth: int):
    """Endless requests: cycle after cycle of `lengths`, each cycle in an
    order of its own."""
    rng = _rng(seed, 1)
    while True:
        for length in rng.permutation(np.asarray(lengths)):
            yield fold_request(rng, int(length), msa_depth)


def open_loop_schedule(seed: int, traffic: dict, seconds: float):
    """[(due seconds from the window's opening, length)] for one window.

    Gaps are the `block` quantiles of an exponential distribution at
    `rate_per_s`, shuffled by the seed: every seed sees the same gaps, and
    every `block / rate_per_s` seconds hold exactly `block` arrivals. That is
    steadier than a Poisson process, whose count in such a stretch would vary
    by 1 / sqrt(block); it is what lets seeds be compared. Lengths fill the mix's shares exactly, each one of
    `length_steps` evenly spaced lengths from its bucket edge down to
    `below_edge` under it (few distinct lengths, so the check's reference
    programs are soon all in the cache); both repeat in blocks of `block`
    arrivals so that any window length holds the same mix."""
    rng = _rng(seed, 2)
    block, rate = int(traffic["block"]), float(traffic["rate_per_s"])
    quantiles = (np.arange(block) + 0.5) / block
    gaps = -np.log1p(-quantiles) / rate
    gaps *= (block / rate) / gaps.sum()          # exact mean 1 / rate
    edges = []
    for edge, share in traffic["length_mix"]:
        edges += [int(edge)] * int(round(share * block))
    edges = (edges + [int(traffic["length_mix"][-1][0])] * block)[:block]
    below, steps = float(traffic["below_edge"]), int(traffic["length_steps"])
    schedule, t = [], 0.0
    while t < seconds:
        for gap, edge in zip(rng.permutation(gaps),
                             rng.permutation(np.asarray(edges))):
            t += float(gap)
            if t >= seconds:
                break
            k = int(rng.integers(0, steps))
            schedule.append(
                (t, int(edge - round(k * below * edge / max(steps - 1, 1)))))
    return schedule


def train_batch(seed: int, step: int, crop: int, msa_depth: int,
                batch: int = 1) -> dict:
    """The training step's input, prepared on the host for every step: random
    residues and MSA, full masks, and a CA trace that is a random walk of
    3.8 Angstrom steps (a protein's own distance distribution). Every step
    of every seed differs."""
    rng = _rng(seed, 1000 + step)
    steps = rng.standard_normal((batch, crop, 3)).astype(np.float32)
    steps *= 3.8 / np.linalg.norm(steps, axis=-1, keepdims=True)
    return {"seq": rng.integers(0, NUM_AMINO_ACIDS, (batch, crop),
                                dtype=np.int32),
            "msa": rng.integers(0, NUM_AMINO_ACIDS, (batch, msa_depth, crop),
                                dtype=np.int32),
            "mask": np.ones((batch, crop), bool),
            "msa_mask": np.ones((batch, msa_depth, crop), bool),
            "coords": np.cumsum(steps, axis=1)}
