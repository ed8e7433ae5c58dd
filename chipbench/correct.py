"""Whether the served tokens are right: the comparison that decides
``correct``.

Once the window has closed, a sample of the requests the window finished
is drawn from the seed, the longest among them always included, until it
holds ``check_tokens`` served tokens.  The plain reference, computed at
the precision the configuration states, runs once over each prompt
followed by its served tokens, and at every position that produced a
served token reads the gap by which that token's logit lies below the
reference's best logit there.  A cell's ``limits`` name the numbers
compared: the mean of those gaps over the sample (``mean_gap``), the
widest (``logit_gap``), or the share of served tokens that are not the
reference's first choice (``off_argmax``).  A program that computes what
the configuration states differs from the reference by the order of its
float32 sums alone, which moves a logit across a rounding boundary now
and then; a wrong page, a skipped layer or a cruder arithmetic (int8
weights) picks tokens the reference ranks below its best far more often.
"""
from __future__ import annotations

import numpy as np


def sample(served: dict, prompts: dict, seed: int, check_tokens: int) -> list:
    """rids to compare: the longest finished request, then others in an
    order drawn from the seed, until ``check_tokens`` tokens are held."""
    if not served:
        return []
    rids = sorted(served)
    longest = max(rids, key=lambda r: (len(prompts[r]) + len(served[r]), r))
    order = [longest] + [r for r in np.random.default_rng(
        [seed % 2**63, 17]).permutation(rids) if r != longest]
    out, n = [], 0
    for r in order:
        out.append(int(r))
        n += len(served[r])
        if n >= check_tokens:
            break
    return out


def sequences(rids, prompts: dict, served: dict):
    """Each request's prompt + served tokens but the last, and the rows
    whose next-token logits produced the served tokens."""
    seqs, rows, toks = [], [], []
    for r in rids:
        p, s = np.asarray(prompts[r]), np.asarray(served[r])
        seqs.append(np.concatenate([p, s[:-1]]).astype(np.int32))
        rows.append(np.arange(len(p) - 1, len(p) - 1 + len(s)))
        toks.append(s.astype(np.int32))
    return seqs, rows, toks


def gaps(logits, tokens) -> np.ndarray:
    """Per row: best logit minus the logit of ``tokens`` at that row."""
    lg = np.asarray(logits, np.float32)
    t = np.asarray(tokens)
    return lg.max(axis=-1) - lg[np.arange(len(t)), t]


def summary(g: np.ndarray) -> dict:
    """The numbers a comparison may hold a set of gaps to: the widest
    (``logit_gap``) and the mean over every compared token
    (``mean_gap``), and the share of tokens that are not the reference's
    first choice."""
    return {"logit_gap": float(g.max()), "mean_gap": float(g.mean()),
            "off_argmax": float((g > 0).mean())}


def served_gap(reference, sizes: dict, seed: int, rids, prompts, served,
               pad_to: int | None = None) -> tuple:
    """(summary of the served tokens' gaps, tokens compared)."""
    seqs, rows, toks = sequences(rids, prompts, served)
    ref = reference.logits(sizes, seed, seqs, rows, pad_to=pad_to)
    g = np.concatenate([gaps(lg, t) for lg, t in zip(ref, toks)])
    return summary(g), int(g.size)
