"""Seconds per round of the window spent in ``core.defta.evaluate`` at the
eval boundaries, by the harness's own clock around each call."""


def read(ctx):
    spans = ctx["window"].spans.get("eval", [])
    return sum(spans) / ctx["rounds"] if spans and ctx["rounds"] else None
