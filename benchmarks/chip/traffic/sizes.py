"""Request sizes and token ids, shared by every traffic kind.

A mix is ``traffic/<mix>.json``; its ``kind`` names the module
``traffic/<kind>.py`` that turns it into load (``run.traffic_kind``).
Sizes come from a fixed stream, so every seed gets the same prompt
lengths, output lengths and prefix choices; a kind may permute them by
the seed. The seed draws the token ids. A seed thus changes which
request is which, not how much work a window holds.

Keys of a mix that this module reads:
  source       the published statistics the sizes follow
  prefixes     distinct shared prefixes (0: prompts share nothing)
  prefix_len   tokens in each shared prefix
  prefix_zipf  Zipf exponent of the choice among prefixes
  prompt       {"median", "sigma", "min", "max"}: lognormal length of
               the part of each prompt that is its own
  output       the same for the tokens to generate
"""
from __future__ import annotations

import itertools

import numpy as np

SIZES_SEED = 20250417          # the fixed stream all seeds share


def _lognormal(rng, d: dict, n: int) -> np.ndarray:
    x = rng.lognormal(np.log(d["median"]), d["sigma"], n)
    return np.clip(np.round(x), d["min"], d["max"]).astype(int)


def sizes(p: dict, n: int):
    """(own prompt lengths, output lengths, prefix index or -1) of ``n``
    requests, the same for every seed."""
    rng = np.random.default_rng(SIZES_SEED)
    own = _lognormal(rng, p["prompt"], n)
    out = _lognormal(rng, p["output"], n)
    if p.get("prefixes", 0):
        k = np.arange(1, p["prefixes"] + 1, dtype=float)
        w = k ** -p["prefix_zipf"]
        which = rng.choice(p["prefixes"], n, p=w / w.sum())
    else:
        which = np.full(n, -1)
    return own, out, which


def requests(p: dict, seed: int, n: int, vocab: int, permute: bool):
    """Endless (prompt, max new tokens): the ``n`` sizes of ``sizes`` in
    their fixed order, or permuted by the seed, then again; token ids
    drawn from the seed."""
    rng = np.random.default_rng(seed)
    own, out, which = sizes(p, n)
    if permute:
        order = rng.permutation(n)
        own, out, which = own[order], out[order], which[order]
    prefixes = rng.integers(0, vocab, (max(p.get("prefixes", 0), 1),
                                       p.get("prefix_len", 0))).tolist()
    for i in itertools.count():
        j = i % n
        head = prefixes[which[j]] if which[j] >= 0 else []
        yield head + rng.integers(0, vocab, own[j]).tolist(), int(out[j])


def prompt_lengths(p: dict) -> tuple[int, int]:
    """(shortest, longest) prompt the mix can send."""
    head = p.get("prefix_len", 0) if p.get("prefixes", 0) else 0
    return head + p["prompt"]["min"], head + p["prompt"]["max"]
