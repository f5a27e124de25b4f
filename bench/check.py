"""The comparison that decides ``correct``.

After the window has closed, a sample of the requests the window finished,
drawn from the seed and always holding the longest, is run through the plain
reference that the configuration names (``cell.reference_module``; the dense
one is reference.py) once over prompt + served tokens.  For each served
token the reading is how far its reference logit lies below the reference's
best logit at that position: 0 where the served token is the reference's
greedy choice, small where rounding let a near-tie flip, large where a token
is wrong.  The readings are the widest such gap, their mean over the served
tokens, and the share of tokens off the reference's first choice; a
configuration's ``check`` names the ones compared, each with its limit.

The control puts the reference in bfloat16 in the program's place: at each
position of the same prompts and tokens it reads the gap of the token the
bfloat16 forward ranks first, and the same limits then decide whether it is
correct.
"""
from __future__ import annotations

import numpy as np

# served tokens the sample aims for; the reference's time grows with it
SAMPLE_TOKENS = 768
SAMPLE_MAX_REQUESTS = 8


def pick(finished, seed: int):
    """finished: list of (prompt, tokens).  The longest, then others in a
    seeded order until the sample holds SAMPLE_TOKENS served tokens."""
    if not finished:
        return []
    order = np.random.default_rng(seed).permutation(len(finished))
    longest = max(range(len(finished)),
                  key=lambda i: len(finished[i][0]) + len(finished[i][1]))
    out, tokens = [finished[longest]], len(finished[longest][1])
    for i in order:
        if tokens >= SAMPLE_TOKENS or len(out) >= SAMPLE_MAX_REQUESTS:
            break
        if i != longest:
            out.append(finished[i])
            tokens += len(finished[i][1])
    return out


def _seqs(sample):
    seqs = []
    for prompt, toks in sample:
        plen, n = len(prompt), len(toks)
        seq = np.concatenate([np.asarray(prompt, np.int64),
                              np.asarray(toks[:-1], np.int64)])
        seqs.append((seq, plen - 1, np.arange(plen - 1, plen - 1 + n)))
    return seqs


def gaps(ref_logits, chosen):
    """Per position: best reference logit minus the chosen token's."""
    return np.concatenate([
        lg.max(-1) - np.take_along_axis(lg, np.asarray(c)[:, None], -1)[:, 0]
        for lg, c in zip(ref_logits, chosen)])


def _summary(g) -> dict:
    return {"widest_logit_gap": float(g.max()),
            "mean_logit_gap": float(g.mean()),
            "off_greedy_share": float((g > 0).mean()),
            "served_tokens": int(g.size)}


def readings(reference, sample, control=None):
    """(program, control): the readings of the served tokens against
    `reference`, and with `control` (a Reference in lower precision) the
    same readings of the control's own first choices at the same positions,
    as if it had served them; None without it."""
    seqs = _seqs(sample)
    ref = reference.logits(seqs)
    program = _summary(gaps(ref, [np.asarray(t) for _, t in sample]))
    program["requests"] = len(sample)
    if control is None:
        return program, None
    ctl = _summary(gaps(ref, [c.argmax(-1) for c in control.logits(seqs)]))
    ctl["requests"] = len(sample)
    return program, ctl
