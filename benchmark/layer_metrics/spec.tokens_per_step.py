"""Engine step: tokens a slot and step of a resident drafter: sum(emitted) /
sum(batch_fill) over the ``decode`` flight records that carry ``rows`` (the
two-row step of a model that drafts for itself: engine/core.py
``_dispatch_rows`` queues it, ``_harvest_verify`` writes its record). 1.00
where no draft is accepted (seeded weights), up to 2.00 where every one is;
``step.decode_per_s`` counts dispatches, which under acceptance are no longer
one token a slot, so this stands beside it. A program without such a step:
nothing to read."""


def read(ctx):
    steps = [r for r in ctx["flight"] if r["kind"] == "decode" and "rows" in r]
    slots = sum(r["batch_fill"] for r in steps)
    return sum(r["emitted"] for r in steps) / slots if slots else None
