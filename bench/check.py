"""Whether what the timed path served is correct.

After the window closes and the program's state is freed, a sample of
the finished requests, drawn from the run's seed and always holding the
longest, goes through the float32 reference of the configuration's
block once: each prompt followed
by the tokens that were served for it. Every served token is greedy, so
its reference logit should be the best at its position, up to the
rounding of the served type. The number compared is the widest gap by
which a served token's reference logit lies below the reference's best.

The first served token comes from prefill, the rest from decode through
the KV cache (in a disaggregated cell, through the cache after it crossed
chips), so one gap covers all three.
"""

from __future__ import annotations

import numpy as np

SAMPLE_TOKENS = 320  # served tokens the sample reaches at least
SAMPLE_MIN, SAMPLE_MAX = 4, 12  # requests in the sample


def sample(finished: list, seed: int) -> list:
    """Finished requests to compare: the longest, then others in an order
    drawn from ``seed``, until the sample holds ``SAMPLE_TOKENS`` served
    tokens and at least ``SAMPLE_MIN`` requests."""
    if not finished:
        return []
    size = [len(s.req.prompt_tokens) + len(s.req.generated) for s in finished]
    first = int(np.argmax(size))
    rest = [i for i in np.random.default_rng(int(seed) + 1).permutation(
        len(finished)) if i != first]
    out, tokens = [finished[first]], len(finished[first].req.generated)
    for i in rest:
        if len(out) >= SAMPLE_MAX or (tokens >= SAMPLE_TOKENS
                                      and len(out) >= SAMPLE_MIN):
            break
        out.append(finished[i])
        tokens += len(finished[i].req.generated)
    return out


def gaps(block, cfg: dict, seed: int, picked: list,
         control: bool = False) -> dict:
    """Widest gap of the served tokens (and, with ``control``, of the
    tokens the float8 control ranks first) below the best of ``block``'s
    reference."""
    seqs, positions, served = [], [], []
    for s in picked:
        prompt = np.asarray(s.req.prompt_tokens, np.int32)
        gen = np.asarray(s.req.generated, np.int32)
        seqs.append(np.concatenate([prompt, gen]))
        positions.append(len(prompt) - 1 + np.arange(len(gen)))
        served.append(gen)
    precisions = ("f32", "fp8") if control else ("f32",)
    lg = block.reference.logits(cfg, seed, seqs, positions, precisions)
    ref = lg["f32"]
    best = [r.max(-1) for r in ref]
    out = {
        "served_gap": max(float((b - r[np.arange(len(t)), t]).max())
                          for b, r, t in zip(best, ref, served)),
        "served_tokens": int(sum(len(t) for t in served)),
    }
    if control:
        out["control_gap"] = max(
            float((b - r[np.arange(len(c)), c]).max())
            for b, r, c in zip(best, ref, (x.argmax(-1) for x in lg["fp8"])))
    return out
