"""Decide ``correct``: the served tokens against the plain reference.

After the window has closed, a sample of the finished window requests
(the longest prompt and others drawn from the seed) is replayed through
the configuration's plain float32 reference (``references/<name>.py``,
found by `archs.reference`): each prompt followed by its served tokens,
one pass.  For every served token the reference gives its logits at
that position; the number compared is the widest
gap by which a served token's logit lies below the reference's best
(`max_gap`, in logits).  Served tokens are greedy, so a sound program
reads a gap of rounding size and a wrong mask, position, layer or cache
entry reads a gap the size of the logits' spread.

The control is the same reference computed in float8 e4m3 in the
program's place (``fp8``: both operands of every matmul, the step below
the configurations' bfloat16): at every position of the same sequences,
the gap of the token the float8 pass puts first.  The
benchmark's runs do not compute it: `readings` reads it when asked
(``controls``), as the tests do, and as a chip run does that sets the
limit's upper reading.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

import archs


def sequences(prompts: Sequence[np.ndarray], served: Sequence[List[int]]):
    """Each prompt followed by all but the last served token: the
    reference's logits at its last len(served) positions predict them."""
    return [np.concatenate([np.asarray(p, np.int64), np.asarray(s[:-1], np.int64)])
            for p, s in zip(prompts, served)]


def gaps(ref_logits: Sequence[np.ndarray], tokens: Sequence[Sequence[int]]) -> np.ndarray:
    """Per token: the reference's best logit minus the token's own."""
    out = []
    for lg, toks in zip(ref_logits, tokens):
        lg = np.asarray(lg, np.float64)
        out.append(lg.max(axis=1) - lg[np.arange(len(toks)), np.asarray(toks)])
    return np.concatenate(out) if out else np.zeros(0)


def readings(cfg: dict, seed: int, prompts, served, controls=()) -> Dict[str, float]:
    """The program's reading on a sample, the widest gap of a served token
    (`max_gap`), and for each precision in `controls` the control's: the
    widest gap of the token that reference, computed in that precision in
    the program's place, puts first at each position of the same
    sequences (``control_gap.<precision>``)."""
    n = min(len(s) for s in served)
    served = [s[:n] for s in served]
    seqs = sequences(prompts, served)
    mod = archs.reference(cfg)
    ref = mod.logits(cfg, seed, seqs, n)
    g = gaps(ref, served)
    out = {"max_gap": float(g.max()), "tokens_compared": int(g.size),
           "tokens_off_top1": int((g > 0).sum())}
    for quant in controls:
        low = mod.logits(cfg, seed, seqs, n, quant=quant)
        c = gaps(ref, [np.argmax(lg, axis=1) for lg in low])
        out[f"control_gap.{quant}"] = float(c.max())
        out[f"control_off_top1.{quant}"] = int((c > 0).sum())
    return out
