"""Decide `correct` for a run, after its window has closed.

Every answer the timed path gave (lead-in, window and the tail it drained)
is compared with the label the generator gave its request: a per-set
verdict through the coalescer, one verdict per block in a direct call. The
labels are then held to the plain reference (perfbench/ref) on a sample of
sets drawn from the seed: always the request with the most keys, and the
tampered sets of the first two tampered requests, where there are any. The
numbers compared, each with limit 0 (an exact comparison):

- `missing`: requests submitted whose answer never came;
- `wrong`: answers that differ from the label;
- `reference_disagreements`: sampled sets whose reference verdict differs
  from the label.

With a control (ref/verify.py) the control's answers take the program's
place on the sampled requests (per set), or on a tampered sampled request
and a valid answered one (whole blocks), and a second set of
numbers counts them in `wrong`; the program's own numbers are kept too.
"""

from __future__ import annotations

import random

from .ref import verify as refv

LIMITS = {"missing": 0, "wrong": 0, "reference_disagreements": 0}


def _expected(stream, i: int, per_set: bool):
    labels = stream.expected(i)
    return labels if per_set else all(labels)


def _ref_set(s, msgs, pks_of):
    return [pks_of(v) for v in s.keys], msgs[s.msg], refv.g2_point(s.sig)


def _sample(stream, answered: list[int], k: int, seed: int) -> list[tuple[int, int]]:
    """(request, set) pairs to hold to the reference."""
    rng = random.Random(f"perfbench:check:{seed}")
    picks: list[tuple[int, int]] = []
    tampered = 0
    for i in answered:
        bad = [j for j, s in enumerate(stream.request(i)) if not s.valid]
        if bad:
            picks += [(i, j) for j in bad]
            tampered += 1
            if tampered == 2:
                break
    i = rng.choice(answered)
    sets = stream.request(i)
    picks.append((i, max(range(len(sets)), key=lambda j: len(sets[j].keys))))
    while len(picks) < k:
        i = rng.choice(answered)
        pick = (i, rng.randrange(len(stream.request(i))))
        if pick not in picks:
            picks.append(pick)
    return picks


def check(stream, result: dict, msgs, pk_xy, k: int, seed: int, per_set: bool, control=None):
    """(numbers, control numbers or None, notes) for the run; numbers map
    to LIMITS."""
    answers = result["answers"]
    answered = sorted(i for i, a in answers.items() if a is not None)
    numbers = {"missing": result["missing"] + result["submitted"] - len(answered)}
    cache: dict[int, object] = {}

    def pks_of(v):
        pt = cache.get(v)
        if pt is None:
            pt = cache[v] = refv.g1_point(pk_xy[v])
        return pt

    picks = _sample(stream, answered, k, seed)
    numbers["reference_disagreements"] = sum(
        refv.verify_set(*_ref_set(stream.request(i)[j], msgs, pks_of)) != stream.request(i)[j].valid
        for i, j in picks
    )
    numbers["wrong"] = sum(answers[i] != _expected(stream, i, per_set) for i in answered)
    notes = {"sampled_sets": len(picks)}
    if control is None:
        return numbers, None, notes
    ctl = dict(numbers)
    if per_set:
        ctl["wrong"] = sum(
            refv.control_set(control, *_ref_set(stream.request(i)[j], msgs, pks_of))
            != stream.request(i)[j].valid
            for i, j in picks
        )
        notes["control_answers"] = len(picks)
    else:
        sampled = list(dict.fromkeys(i for i, _ in picks))
        tampered = [i for i in sampled if not all(stream.expected(i))][:1]
        valid = next((i for i in sampled + answered if all(stream.expected(i))), None)
        requests = tampered + ([] if valid is None else [valid])
        ctl["wrong"] = sum(
            refv.control_request(control, [_ref_set(s, msgs, pks_of) for s in stream.request(i)])
            != _expected(stream, i, per_set)
            for i in requests
        )
        notes["control_answers"] = len(requests)
    return numbers, ctl, notes
