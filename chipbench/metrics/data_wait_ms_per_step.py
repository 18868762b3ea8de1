"""Mean length of the benchmark's span around the hand-over of a batch
(host clock, whole window)."""


def read(ctx):
    spans = ctx["spans"].get("feed")
    if not spans:
        return None
    return 1e3 * sum(e - s for s, e in spans) / len(spans)
