"""The comparison that decides ``correct``.

Once the window has closed, a sample of the requests the engine finished,
drawn from the seed and always holding the one with the most served
tokens, goes through the float32 reference: each prompt with its served
tokens, teacher-forced.  At every served token the reference's best logit
minus the logit of the token the engine served is that token's gap; the
number compared is the widest gap.  Greedy serving of the reference's own
model would read 0, up to near-ties that rounding may flip.
"""
from __future__ import annotations

from typing import List, Tuple

import numpy as np

from bench import reference


def sample(finished: List[Tuple[np.ndarray, list]], k: int, seed: int):
    """``k`` of the finished (prompt, tokens) pairs: the one with the most
    served tokens (then the longest prompt) and k - 1 drawn from the
    seed."""
    if not finished:
        return []
    order = sorted(range(len(finished)),
                   key=lambda i: (-len(finished[i][1]),
                                  -len(finished[i][0]), i))
    rest = order[1:]
    rng = np.random.default_rng(seed)
    pick = rng.permutation(len(rest))[:k - 1]
    return [finished[order[0]]] + [finished[rest[i]] for i in sorted(pick)]


def _arrays(cfg, k, r, pairs):
    """(k, max_seq) sequences and (k, r) rows, tokens and mask: ``r`` is
    the most tokens a request of the mix is served."""
    t = cfg["engine"]["max_seq"]
    seqs = np.zeros((k, t), np.int32)
    rows = np.zeros((k, r), np.int32)
    toks = np.zeros((k, r), np.int32)
    mask = np.zeros((k, r), bool)
    for i, (prompt, out) in enumerate(pairs):
        p, n = len(prompt), len(out)
        if p + n > t or n > r:
            raise ValueError(f"request of {p} + {n} tokens does not fit the "
                             f"check's {t} positions and {r} rows")
        seqs[i, :p] = prompt
        seqs[i, p:p + n] = out
        rows[i, :n] = np.arange(p - 1, p + n - 1)
        toks[i, :n] = out
        mask[i, :n] = True
    return seqs, rows, toks, mask


def compare(cfg: dict, sample_size: int, rows: int, seed: int, pairs,
            control: bool = False) -> dict:
    """Gaps of the served tokens of ``pairs`` against the reference.  With
    ``control``, also the gap of the token the fp8 reference puts first at
    each of the same positions."""
    if not pairs:
        return {"max_gap": float("inf"), "tokens": 0}
    seqs, rows, toks, mask = _arrays(cfg, sample_size, rows, pairs)
    h, head = reference.hidden(seed, cfg, seqs, rows)
    gap, top = reference.gaps(h, head, toks)
    out = {"max_gap": float(gap[mask].max()), "tokens": int(mask.sum()),
           "argmax_share": float((toks[mask] == top[mask]).mean())}
    if control:
        hc, _ = reference.hidden(seed, cfg, seqs, rows, quant="fp8")
        _, ctop = reference.gaps(hc, head, toks, quant="fp8")
        cgap, _ = reference.gaps(h, head, ctop)
        out["control_max_gap"] = float(cgap[mask].max())
        out["control_argmax_share"] = float((ctop[mask] == top[mask]).mean())
    return out


def verdict(numbers: dict, check_spec: dict) -> Tuple[bool, dict]:
    """(every compared number within its limit, {name: (value, limit)})."""
    lines = {n: (numbers[n], lim["limit"])
             for n, lim in check_spec["limits"].items()}
    ok = all(v <= lim for v, lim in lines.values())
    return ok, lines

