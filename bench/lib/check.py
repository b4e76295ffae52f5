"""The comparison that decides `correct`: served tokens against the plain
reference.

Once the window has closed, a sample of the requests that the timed path
finished, drawn from the seed and always holding the longest, is run
through the reference: each prompt followed by its served tokens, in one
teacher-forced pass. At every position that produced a served token, the
gap is how far that token's reference logit lies below the reference's best
logit there. A greedy server that computes what the configuration states
picks the best token or one within rounding of it; the number compared is
the widest gap over the sample.

The lower-precision control reads the same prompts and tokens through the
reference computed in fp8 and takes, at each position, the token that the
fp8 logits put first: its gap under the float32 reference is what a server
computing in fp8 would show.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np


def sample_requests(finished: Sequence, seed: int, rows: int,
                    target_tokens: int) -> List:
    """Up to `rows` finished requests: the longest, then others in an order
    drawn from the seed, until `target_tokens` served tokens are held."""
    if not finished:
        return []
    reqs = sorted(finished, key=lambda r: (len(r.prompt) + len(r.output),
                                           r.request_id))
    chosen = [reqs.pop()]
    rng = np.random.default_rng([int(seed), 7])
    for i in rng.permutation(len(reqs)):
        if len(chosen) >= rows or sum(len(r.output) for r in chosen) >= target_tokens:
            break
        chosen.append(reqs[i])
    return chosen


def batch(chosen: Sequence, rows: int, t_max: int):
    """Token rows (prompt, then every served token but the last), their
    lengths, the (row, position) of each served token, and the tokens."""
    tokens = np.zeros((rows, t_max), np.int32)
    lengths = np.zeros(rows, np.int64)
    r_idx, c_idx, served = [], [], []
    for r, req in enumerate(chosen):
        p = np.asarray(req.prompt, np.int32).reshape(-1)
        out = np.asarray(req.output, np.int32)
        seq = np.concatenate([p, out[:-1]])
        assert len(seq) <= t_max, (len(seq), t_max)
        tokens[r, :len(seq)] = seq
        lengths[r] = len(seq)
        for j in range(len(out)):
            r_idx.append(r)
            c_idx.append(len(p) - 1 + j)
            served.append(int(out[j]))
    return (tokens, lengths, np.asarray(r_idx, np.int32),
            np.asarray(c_idx, np.int32), np.asarray(served, np.int32))


@jax.jit
def _gaps(logits, tokens):
    best = jnp.max(logits, axis=-1)
    at = jnp.take_along_axis(logits, tokens[:, None], axis=-1)[:, 0]
    return best - at


def gaps(ref_logits, tokens) -> np.ndarray:
    """Reference best logit minus the reference logit of each token."""
    return np.asarray(_gaps(ref_logits, jnp.asarray(tokens, jnp.int32)))


def served_gap(reference, seed: int, chosen: Sequence, rows: int,
               t_max: int, control=None) -> Dict:
    """The widest gap of the served tokens (and, given the fp8 `control`
    reference, of the tokens the control puts first)."""
    tokens, lengths, r_idx, c_idx, served = batch(chosen, rows, t_max)
    ref = reference.logits(seed, tokens, lengths, r_idx, c_idx)
    g = gaps(ref, served)
    out = {"served_tokens": int(len(served)), "requests": len(chosen),
           "max_gap": float(g.max()), "mean_gap": float(g.mean()),
           "mismatch_share": float(np.mean(g > 0))}
    if control is not None:
        low = control.logits(seed, tokens, lengths, r_idx, c_idx)
        pick = np.asarray(jnp.argmax(low, axis=-1))
        gc = gaps(ref, pick)
        out.update(control_max_gap=float(gc.max()),
                   control_mean_gap=float(gc.mean()),
                   control_mismatch_share=float(np.mean(gc > 0)))
    return out
