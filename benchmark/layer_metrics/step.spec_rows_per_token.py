"""Engine step: rows scored a token emitted by a resident drafter's step:
sum(rows) / sum(emitted) over the ``decode`` flight records that carry
``rows``. 2.0 where no draft is accepted (every second row is rewound), 1.0
where every one is: what a token costs in scored rows. A program without such
a step: nothing to read."""


def read(ctx):
    steps = [r for r in ctx["flight"] if r["kind"] == "decode" and "rows" in r]
    emitted = sum(r["emitted"] for r in steps)
    return sum(r["rows"] for r in steps) / emitted if emitted else None
