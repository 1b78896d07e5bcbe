"""Share of the decode slots' steps that were fed from the device behind an
un-harvested dispatch: 100 * sum(chained) / sum(batch_fill) over the
``decode`` flight records. At one step per dispatch the engine launches step
n+1 off step n's on-device tokens before it fetches them; a slot's first step
after admission is fed from the host, and a step in flight that is known to
be a slot's last has none launched behind it. A program without the counter
records no ``chained``: nothing to read."""


def read(ctx):
    records = [r for r in ctx["flight"]
               if r["kind"] == "decode" and "chained" in r]
    fill = sum(r["batch_fill"] for r in records)
    if not fill:
        return None
    return 100.0 * sum(r["chained"] for r in records) / fill
