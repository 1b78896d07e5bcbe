"""Share of the admitted prompts' tokens that the device prefix cache held:
100 * sum(hit_device) / sum(prompt) over the window's ``prefill`` flight
records. Where every caller asks about one of a few long documents, each
document is prefilled once and every later prompt is a hit but for its own
question (~98% at 16,384 shared of ~16,540). No prefill in the window, or no
hit at all (a mix without shared prefixes): nothing to read."""


def read(ctx):
    records = [r for r in ctx["flight"]
               if r["kind"] == "prefill" and "hit_device" in r]
    prompt = sum(r["prompt"] for r in records)
    hit = sum(r["hit_device"] for r in records)
    if not prompt or not hit:
        return None
    return 100.0 * hit / prompt
