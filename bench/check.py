"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample
of the requests that the timed path finished, drawn from the seed and
always holding the request with the most served tokens, goes through the
configuration's plain reference (``bench/references/<reference>.py``):
one forward over each prompt followed by its served tokens. At every
position that produced a served token the reference's best logit is
compared with its logit of the served token. The number compared is the
widest such gap over all sampled positions: 0 where the program chose
what the reference chooses, and about the spread of the top logits where
it chose something else.

The control (:func:`control_gaps`) reads the same positions, but of the
token that the reference computed in the next lower precision (int4
activations for int8) puts first there: the gap that a program which
took that precision would show.
"""
from __future__ import annotations

import importlib

import jax.numpy as jnp
import numpy as np


def sample(done: list, seed: int, n: int) -> list:
    """``n`` finished requests: the one with the most served tokens, then
    others drawn from the seed."""
    if not done:
        return []
    longest = max(range(len(done)), key=lambda i: len(done[i].tokens))
    rest = [i for i in range(len(done)) if i != longest]
    rng = np.random.default_rng([seed, 0xC0FFEE])
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [done[longest]] + [done[rest[int(i)]] for i in sorted(pick)]


def inputs(recs: list, length: int):
    """-> tokens (n, length), rows (n, P), served (n, P), mask (n, P):
    each prompt followed by its served tokens but the last, the position
    that produced each served token, the token, and which are real."""
    P = max(len(r.tokens) for r in recs)
    tokens = np.zeros((len(recs), length), np.int32)
    rows = np.zeros((len(recs), P), np.int32)
    served = np.zeros((len(recs), P), np.int32)
    mask = np.zeros((len(recs), P), bool)
    for i, r in enumerate(recs):
        seq = list(r.prompt) + list(r.tokens[:-1])
        tokens[i, :len(seq)] = seq
        n = len(r.tokens)
        rows[i, :n] = len(r.prompt) - 1 + np.arange(n)
        rows[i, n:] = rows[i, n - 1]
        served[i, :n] = r.tokens
        mask[i, :n] = True
    return tokens, rows, served, mask


def reference_logits(cfg: dict, seed: int, tokens, rows, *, bits: int):
    """(n, P, vocab) float32 on the device."""
    mod = importlib.import_module(f"bench.references.{cfg['reference']}")
    return mod.logits(cfg, seed, tokens, rows, bits=bits)


def position_gaps(logits, chosen, mask) -> np.ndarray:
    """(best logit - logit of ``chosen``) at every masked position, on
    the host: 0 where ``chosen`` is the reference's best."""
    got = jnp.take_along_axis(logits, jnp.asarray(chosen)[..., None],
                              axis=-1)[..., 0]
    gap = np.asarray(jnp.max(logits, axis=-1) - got)
    return gap[np.asarray(mask)]


def control_gaps(logits, low, mask) -> np.ndarray:
    """The gaps, under the reference, of the tokens that the lower
    precision reference ``low`` puts first."""
    return position_gaps(logits, jnp.argmax(low, axis=-1), mask)


#: The numbers a configuration may compare (its ``correct`` block names
#: each with its limit), over the gaps of every compared position.
NUMBERS = {
    "widest_logit_gap": lambda g: float(np.max(g)),
    "mean_logit_gap": lambda g: float(np.mean(g)),
}
