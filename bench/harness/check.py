"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample of
the requests the timed path finished, drawn from the seed and holding the
longest, is run through the plain reference: one full forward pass over
each prompt with its served tokens. At every served position the gap is
the reference's best logit minus the reference's logit of the token that
was served (0 where they agree). The number compared is the widest gap.

The control puts the reference computed in float8 in the program's place:
at the same positions of the same sequences it reads the gap of the token
that float8 puts first.
"""
from __future__ import annotations

import random
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from harness import weights


def sample(reqs: list, seed: int, n: int) -> list:
    """Up to ``n`` finished requests: the one with the most served tokens
    (then the longest sequence) and others drawn from the seed."""
    done = [r for r in reqs if r.generated and r.state.value == "done"]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.generated),
                                       len(r.prompt) + len(r.generated)))
    rest = [r for r in done if r is not longest]
    rng = random.Random(seed)
    return [longest] + rng.sample(rest, min(len(rest), n - 1))


def gaps(cell, seed: int, reqs: list,
         quant: Optional[str] = None) -> Tuple[float, int]:
    """Widest gap over the served tokens of ``reqs`` (or, with ``quant``,
    of the tokens the reference at that precision puts first), and the
    number of tokens compared."""
    d = cell.dims
    ref = d.family
    seqs = [list(r.prompt) + list(r.generated) for r in reqs]
    layer = weights.layer_fn(d, seed)
    top = weights.top_of(d, seed)
    picks = [None] * len(seqs)
    if quant:
        picks = [_argmax(x) for x in
                 ref.logits(d, layer, top, seqs, quant=quant)]
    exact = ref.logits(d, layer, top, seqs)
    per = []
    for r, seq, lg, pick in zip(reqs, seqs, exact, picks):
        S = lg.shape[0]
        served = np.zeros(S, np.int32)  # row p holds the token at p + 1
        served[:len(seq) - 1] = seq[1:]
        mask = np.zeros(S, bool)
        mask[len(r.prompt) - 1:len(seq) - 1] = True
        per.append(float(_widest(lg, served if pick is None else pick,
                                 mask)))
    n = sum(len(r.generated) for r in reqs)
    return (max(per) if per else float("nan")), n


@jax.jit
def _argmax(lg):
    return jnp.argmax(lg, axis=-1).astype(jnp.int32)


@jax.jit
def _widest(lg, picks, mask):
    got = jnp.take_along_axis(lg, picks[:, None], axis=-1)[:, 0]
    return jnp.max(jnp.where(mask, jnp.max(lg, axis=-1) - got, 0.0))
