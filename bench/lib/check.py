"""Whether what the window served is right: a sample of its finished
requests, the longest among them and one from every slot the sample can
reach, against the plain reference.

The reference runs once over each sampled prompt followed by the tokens the
program served for it, and reads, at every position that chose a served
token, how far that token's logit lies below the reference's best.  The
tokens are greedy, so a sound program's gap is zero except where bfloat16
rounding flips a near tie; a wrong cache row, a stale state or an altered
token puts the served token far below the best.  The widest gap is the
number compared; its limit for a cell sits in ``bench/limits/<cell>.json``
with the readings it was set from.
"""
from __future__ import annotations

import dataclasses
import importlib

import numpy as np

from bench.lib import traffic


@dataclasses.dataclass
class Served:
    """One finished request: its prompt, the tokens served for it, and
    the slot that served it (-1 where unknown)."""
    prompt: np.ndarray
    tokens: list
    slot: int = -1


def sample(served: dict, n: int, seed: int) -> list:
    """Up to ``n`` of the finished requests (``served``: id -> Served),
    drawn from the seed: always the one of most positions, then one from
    each slot not yet in the sample, then any."""
    ids = sorted(served)
    if not ids:
        return []
    size = [len(served[r].prompt) + len(served[r].tokens) for r in ids]
    longest = max(range(len(ids)), key=lambda i: (size[i], -i))
    order = [i for i in traffic.rng_for(seed, 99).permutation(len(ids))
             if i != longest]
    picked, slots = [longest], {served[ids[longest]].slot}
    for i in order:
        if served[ids[i]].slot not in slots:
            picked.append(i)
            slots.add(served[ids[i]].slot)
    picked += [i for i in order if i not in picked]
    return [served[ids[i]] for i in picked[:max(1, n)]]


def reference(name: str):
    return importlib.import_module(f"bench.references.{name}")


def compare(ref, seed: int, w, picked: list, pad_to: int,
             control: bool = False) -> dict:
    """Gaps under the float32 reference of the served tokens and, with
    ``control``, of the tokens the control puts first at the same
    positions."""
    seqs = [list(p.prompt) + list(p.tokens[:-1]) for p in picked]
    starts = [len(p.prompt) - 1 for p in picked]
    served = [np.asarray(p.tokens, np.int32) for p in picked]
    sets = [served]
    if control:
        _, _, ctrl = ref.score(seed, w, seqs, starts, [served], pad_to,
                               control=True)
        cuts = np.cumsum([len(s) for s in served])[:-1]
        sets.append(np.split(ctrl, cuts))
    best, got, arg = ref.score(seed, w, seqs, starts, sets, pad_to)
    gaps = best[None] - got
    out = {"tokens_compared": int(gaps.shape[1]),
           "requests_compared": len(picked),
           "max_logit_gap": float(gaps[0].max()),
           "mean_logit_gap": float(gaps[0].mean()),
           "differ_from_argmax": int((arg != np.concatenate(served)).sum())}
    if control:
        out["control_max_logit_gap"] = float(gaps[1].max())
    return out


def verdict(read: dict, served_ids_ok: bool, limits: dict) -> tuple:
    """(correct, checks): each number compared beside its limit."""
    checks = {
        "max_logit_gap": (read.get("max_logit_gap", float("inf")),
                          limits["max_logit_gap"]),
        "tokens_compared": (read.get("tokens_compared", 0),
                            limits["min_tokens_compared"]),
        "token_ids_in_range": (int(served_ids_ok), 1),
    }
    correct = (checks["max_logit_gap"][0] <= checks["max_logit_gap"][1]
               and checks["tokens_compared"][0] >= checks["tokens_compared"][1]
               and served_ids_ok)
    return correct, checks
