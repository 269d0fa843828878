"""Percent of the propagation layout's slot loads that carry a call edge:
the ``edges`` over the ``slots`` of a round (every slot of every ELL row,
pad slots and the loads of split rows' aux rows included), summed over the
window's ``ufa.graph.certify`` and ``ufa.graph.ensemble`` spans.  None
where the program does not count them."""

from harness import spans

SPANS = ("ufa.graph.certify", "ufa.graph.ensemble")


def read(ctx):
    found = [e for name in SPANS for e in spans.spans(ctx.trace, name)
             if "slots" in e.stats and "edges" in e.stats]
    slots = sum(float(e.stats["slots"]) for e in found)
    if not slots:
        return None
    return 100.0 * sum(float(e.stats["edges"]) for e in found) / slots
