"""One general traffic generator; a mix is a data file of parameters.

A mix file (``traffic/<mix>.json``) holds:

* ``loop``: ``"closed"``, the one kind the generator knows: one client per
  slot, each sends its next request when its last one finished;
* ``prompt_bands``: ``[[band, weight], ...]``.  A band is a prompt bucket, a
  multiple of the chunk size ``n_b``; a raw length is drawn from the
  ``n_b - 1`` lengths just below it, never an exact multiple, so each band
  compiles one prefill program;
* ``output``: ``[lo, hi]``, output tokens drawn uniformly, inclusive;
* ``requests``: requests per client;
* ``first_output_from_one``: each client's first request
  gets an output length drawn from 1 up to its own draw, so slots turn over
  out of step from the start;
* ``size_seed``: fixes the sizes and gaps.

Every seed asks for the same work: the sizes are drawn from ``size_seed``
alone.  The run's seed draws the token ids and which client (slot) gets
which stream of sizes.  So runs on
different seeds differ in the order the same work reaches the slots, not in
the work.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Req:
    rid: int
    client: int            # the client (one per slot)
    prompt: np.ndarray     # int32 token ids, raw length
    output: int            # tokens to generate (max_new_tokens)


def rng_for(seed: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence(abs(int(seed))))


def sizes(mix: dict, n: int, chunk: int) -> tuple[np.ndarray, np.ndarray]:
    """The mix's fixed (prompt length, output length) pairs: band counts
    follow the weights, everything drawn from ``size_seed``."""
    rng = rng_for(mix.get("size_seed", 0))
    bands = np.array([b for b, _ in mix["prompt_bands"]], np.int64)
    w = np.array([x for _, x in mix["prompt_bands"]], np.float64)
    if np.any(bands % chunk) or np.any(bands <= chunk):
        raise ValueError(f"bands {bands.tolist()} must be multiples of "
                         f"{chunk} above {chunk}")
    counts = np.floor(w / w.sum() * n).astype(np.int64)
    counts[np.argmax(w)] += n - counts.sum()
    band = rng.permutation(np.repeat(bands, counts))
    plen = band - rng.integers(1, chunk, n)          # band-(n_b-1) .. band-1
    lo, hi = mix["output"]
    return plen, rng.integers(lo, hi + 1, n)


def generate(mix: dict, seed: int, vocab: int, chunk: int,
             clients: int) -> list[Req]:
    """Requests of a mix for one run: ``clients`` streams of
    ``mix["requests"]`` each; request ``i`` of the result belongs to client
    ``req.client`` and comes in stream order."""
    if mix["loop"] != "closed":
        raise ValueError(f"no generator for a {mix['loop']!r} loop")
    rng = rng_for(seed)
    per = mix["requests"]
    n = per * clients
    plen, out = sizes(mix, n, chunk)
    stream = np.arange(n) // per                 # fixed streams ...
    client = rng.permutation(clients)[stream]    # ... dealt to clients
    if mix.get("first_output_from_one"):
        first = np.arange(0, n, per)
        frac = rng_for(mix.get("size_seed", 0) + 1).random(clients)
        out[first] = 1 + (frac * out[first]).astype(np.int64)
    return [Req(rid=i, client=int(client[i]),
                prompt=rng.integers(0, vocab, int(plen[i]), dtype=np.int32),
                output=int(out[i]))
            for i in range(n)]


def max_context(mix: dict) -> int:
    """Most cache tokens one request of the mix holds."""
    return max(b for b, _ in mix["prompt_bands"]) - 1 + mix["output"][1] - 1
