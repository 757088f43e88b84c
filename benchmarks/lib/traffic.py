"""The one traffic generator. A traffic mix is a JSON file of parameters; the
generator turns it and ``--seed`` into the prompts and PRNG keys of each
request. Every seed gives the same sizes (prompt lengths, groups, steps), in
another order and with other words, so the seed does not change the work.

(The arrival processes of ``tools/loadgen.py:generate_stream`` are not
copied: no cell proved so far has arrivals. The cell that brings them copies
them here.)
"""

from __future__ import annotations

import random

#: Words of at most 8 letters, so that each is one token of the hash
#: tokenizer; no two of them collide in either vocabulary (tested).
ARTICLES = ("a", "the", "one", "my")
ADJECTIVES = ("red", "small", "old", "happy", "wet", "tall", "dark", "shiny",
              "wild", "calm", "young", "bright")
ANIMALS = ("squirrel", "fox", "cat", "dog", "owl", "horse", "rabbit", "tiger",
           "panda", "otter", "heron", "lizard")
VERBS = ("eating", "holding", "guarding", "painting", "carrying", "watching",
         "chasing", "washing")
OBJECTS = ("burger", "lasagna", "apple", "pretzel", "lantern", "teapot",
           "pumpkin", "violin", "basket", "cactus", "candle", "balloon")
PLACES = ("forest", "kitchen", "garden", "desert", "harbor", "library",
          "meadow", "cellar")
EXTRAS = ("tasty", "golden", "huge", "tiny", "striped", "frozen", "dusty",
          "glowing")


def prompt_pair(rng: random.Random, kind: str):
    """A source prompt and its edit: ``replace`` swaps one word for another
    of its class (equal length in tokens), ``refine`` adds adjectives."""
    art, adj, animal, verb, obj, place = (
        rng.choice(ARTICLES), rng.choice(ADJECTIVES), rng.choice(ANIMALS),
        rng.choice(VERBS), rng.choice(OBJECTS), rng.choice(PLACES))
    source = f"{art} {adj} {animal} {verb} a {obj} in the {place}"
    if kind == "replace":
        if rng.random() < 0.5:
            new = rng.choice([o for o in OBJECTS if o != obj])
            target = f"{art} {adj} {animal} {verb} a {new} in the {place}"
        else:
            new = rng.choice([a for a in ANIMALS if a != animal])
            target = f"{art} {adj} {new} {verb} a {obj} in the {place}"
    elif kind == "refine":
        e1, e2 = rng.sample(EXTRAS, 2)
        target = f"{art} {adj} {animal} {verb} a {e1} {obj} in the {e2} {place}"
    else:
        raise ValueError(f"unknown edit kind {kind!r}")
    return source, target


class Requests:
    """Request ``i`` of a run: ``groups`` prompt pairs of the mix's edit kind
    and a PRNG key (two uint32). The same ``(seed, i)`` gives the same request
    whenever it is asked for, so the output check can make it again."""

    def __init__(self, traffic: dict, seed: int):
        self.traffic, self.seed = traffic, seed

    def __call__(self, i: int) -> dict:
        rng = random.Random(self.seed * 1_000_003 + i)
        kinds = self.traffic["edit"]["kinds"]
        kind = kinds[i % len(kinds)]
        groups = self.traffic.get("groups", 1)
        return {
            "index": i,
            "kind": kind,
            "prompts": [prompt_pair(rng, kind) for _ in range(groups)],
            "key": (rng.getrandbits(32), rng.getrandbits(32)),
        }
