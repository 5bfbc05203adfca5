"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample of
the finished requests, drawn from the seed and always holding the one with
the longest prompt, is run through the plain reference (the family's
``logits``) over each prompt followed by its served tokens.  At every
served position the reference's float32 logits give the gap by which the
served token lies below the reference's best token.  The run's
``logit_gap`` is the widest such gap; it is held to the cell's limit
(``bench/limits/<cell>.json``).
``failed_requests`` counts requests that did not finish ``done``; its
limit is 0.

The control (``control_gap``) puts the reference computed in float8 in the
program's place: at the same positions of the same prompts and tokens it
reads the gap of the token the float8 logits put first.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

from bench import traffic


def sample(requests: Sequence, seed: int, min_tokens: int,
           max_requests: int) -> List:
    """The finished requests the reference re-runs: the longest prompt
    first, then others drawn from the seed until ``min_tokens`` served
    tokens are covered or ``max_requests`` are taken."""
    done = [r for r in requests
            if r.state == "done" and len(r.output_tokens)]
    if not done:
        return []
    done.sort(key=lambda r: (-len(r.prompt), r.uid))
    picked, rest = [done[0]], done[1:]
    order = traffic.rng(seed, traffic.SAMPLE_STREAM).permutation(len(rest))
    for i in order:
        if (len(picked) >= max_requests
                or sum(len(r.output_tokens) for r in picked) >= min_tokens):
            break
        picked.append(rest[i])
    return picked


def numbers(gap: float, failed: int, limits: Dict) -> Dict:
    """The numbers compared, each with its limit."""
    return {"logit_gap": {"value": gap, "limit": limits["logit_gap"]},
            "failed_requests": {"value": failed, "limit": 0}}


def correct(compared: Dict, picked: Sequence) -> bool:
    """A run is correct when it finished requests to compare and every
    number lies within its limit."""
    return bool(picked) and all(v["value"] <= v["limit"]
                                for v in compared.values())


def served_rows(prompt_len: int, n_out: int):
    """Sequence fed to the reference is prompt + served[:-1]; the logits
    that chose served token j sit at position prompt_len - 1 + j."""
    return np.arange(prompt_len - 1, prompt_len - 1 + n_out)


def widest_gap(ref_logits: np.ndarray, tokens: np.ndarray) -> float:
    """Largest ``best - logit[token]`` over rows (0 where the token is the
    reference's best)."""
    best = ref_logits.max(axis=-1)
    got = np.take_along_axis(ref_logits, tokens[:, None], axis=-1)[:, 0]
    return float(np.max(best - got))


def reference_gaps(family, params, sizes: Dict, picked: Sequence, *,
                   control: bool = False, pad: int = 1024) -> Dict:
    """Widest gap of the served tokens (and, with ``control``, of the
    float8 reference's first choices) over the picked requests, by the
    ``family``'s reference."""
    gap, ctl, first = 0.0, 0.0, 0.0
    tokens = 0
    for r in picked:
        out = np.asarray(r.output_tokens, np.int64)
        seq = np.concatenate([np.asarray(r.prompt, np.int64), out[:-1]])
        rows = served_rows(len(r.prompt), len(out))
        ref = family.logits(params, sizes, seq, rows, pad=pad)
        gap = max(gap, widest_gap(ref, out))
        first = max(first, widest_gap(ref[:1], out[:1]))
        if control:
            low = family.logits(params, sizes, seq, rows, precision="fp8",
                                pad=pad)
            ctl = max(ctl, widest_gap(ref, low.argmax(-1)))
        tokens += len(out)
    res = {"logit_gap": gap, "first_token_gap": first,
           "requests": len(picked), "tokens": tokens}
    if control:
        res["control_gap"] = ctl
    return res
