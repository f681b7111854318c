"""The one traffic generator: the requests of a closed loop's clients from a mix file and
a seed.

Every seed gets the same lengths in the same order, so the work of a run does not depend
on the seed: with the order drawn from the seed ``gen_tok_per_s`` swung by 10 % from seed
to seed (my chip runs, PR 25). The seed draws the token ids (and, elsewhere, the weights)."""
import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name):
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def _grid(spec, n, shift):
    """``n`` whole numbers at even quantiles of the distribution in ``spec``."""
    if spec["dist"] != "uniform":
        raise ValueError(f"unknown distribution {spec['dist']!r}")
    lo, hi = spec["lo"], spec["hi"]
    v = lo + (np.arange(n) + shift) / n * (hi - lo)
    return np.clip(np.rint(v), lo, hi).astype(int)


def waves(mix, seed, vocab):
    """Without end: the next request of every client, ``{"client", "prompt",
    "max_tokens"}``, a wave at a time. A wave's prompt and output lengths lie at even
    quantiles of the mix's distributions, paired and dealt to the clients in a fixed
    shuffle; a client sends its next when its last one ends."""
    if mix["kind"] != "closed":
        raise ValueError(f"no requests in a mix of kind {mix['kind']!r}")
    rs = np.random.RandomState(np.random.SeedSequence(int(seed)).generate_state(4))
    fixed = np.random.RandomState(20250930)
    c = mix["clients"]
    pairing = np.random.RandomState(c).permutation(c)
    w = 0
    while True:
        shift = ((2 * w + 1) % 8) / 8.0
        pairs = list(zip(_grid(mix["prompt"], c, shift), _grid(mix["output"], c, shift)[pairing]))
        yield [{"client": client, "max_tokens": int(pairs[i][1]),
                "prompt": rs.randint(0, vocab, int(pairs[i][0])).tolist()}
               for client, i in enumerate(fixed.permutation(c))]
        w += 1
